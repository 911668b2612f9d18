"""Host-to-device upload probe: what a source partition's upload costs
per call, per byte and per output array on the attached device (PERF.md,
PR 28: the readings behind `ColumnarBatch.chunks_from_numpy`).

Times, for one lineitem partition of q6's four columns (DATE32 + three
FLOAT64: data, validity and float32 shadows, 11 arrays), the chunked
upload (one `jnp.asarray` an array a chunk), whole-column puts, one
`device_put` of the list, the on-device split back into chunks, and
what the split's time is made of (as many outputs with nothing copied;
as many bytes in one output an array).

    chiprun --chips 1 -- python scripts/upload_probe.py --rows 750000,3000000
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, reps: int) -> dict:
    import jax
    jax.block_until_ready(fn())          # warm (compiles, first-touch)
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        ms.append(((time.perf_counter() - t0) * 1e3, (t1 - t0) * 1e3))
        del out
    done = sorted(m[0] for m in ms)
    return {"median_ms": statistics.median(done), "min_ms": done[0],
            "returned_ms": statistics.median(m[1] for m in ms)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="750000",
                    help="partition lengths, comma-separated")
    ap.add_argument("--chunk", type=int, default=65_536)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import spark_rapids_tpu  # noqa: F401  x64 on, compile cache
    out = [_probe(int(n), args) for n in args.rows.split(",")]
    print(json.dumps(out, indent=1))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/upload_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0 if all(o["split_equal"] for o in out) else 1


def _probe(n: int, args) -> dict:
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.vector import _f32_shadow, bucket_capacity
    from spark_rapids_tpu.models.tpch_data import SCHEMAS
    from spark_rapids_tpu.plan.transitions import host_columns_from_df
    from benchmark.gen import tpch as G

    dev = jax.devices()[0]
    chunk = args.chunk
    names = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
    li = G.generate(args.seed, 2 * n, tables=("lineitem",))["lineitem"]
    df = li[names].iloc[:n]
    schema = T.Schema(tuple(SCHEMAS["lineitem"].field(c) for c in names))

    t0 = time.perf_counter()
    data, validity = host_columns_from_df(df, schema)
    convert_ms = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    shadows = {c: _f32_shadow(data[c]) for c in names[1:]}
    shadow_ms = (time.perf_counter() - t0) * 1e3
    whole = [data[c] for c in names] + [validity[c] for c in names] + \
        [shadows[c] for c in names[1:]]
    nbytes = sum(a.nbytes for a in whole)
    los = list(range(0, n, chunk))
    tail = n - los[-1]
    tail_cap = bucket_capacity(tail)

    def pad(a, cap):
        return a if len(a) == cap else np.pad(a, (0, cap - len(a)))

    chunked = [[pad(a[lo:lo + chunk], chunk if lo != los[-1] else tail_cap)
                for a in whole] for lo in los]

    def split(arrs):
        return [[a[lo:lo + chunk] for a in arrs] for lo in los[:-1]] + \
            [[jnp.pad(a[los[-1]:], (0, tail_cap - tail)) for a in arrs]]

    split_jit = jax.jit(split)
    t0 = time.perf_counter()
    on_dev = jax.device_put(whole)
    compiled = split_jit.lower(on_dev).compile()
    split_compile_s = time.perf_counter() - t0
    # equality of the two routes, once
    ref = [[np.asarray(jnp.asarray(a)) for a in c] for c in chunked]
    got = jax.device_get(compiled(on_dev))
    same = all(np.array_equal(x, y, equal_nan=True)
               for rc, gc in zip(ref, got) for x, y in zip(rc, gc))

    # body + tail: full chunks as a view, the tail padded on the host
    body_n = los[-1]
    body = [a[:body_n] for a in whole]
    tail_host = chunked[-1]

    def split_body(arrs):
        return [[a[lo:lo + chunk] for a in arrs] for lo in los[:-1]]
    split_body_jit = jax.jit(split_body)

    host_rows = {c: host_columns_from_df(df.iloc[lo:lo + chunk], schema)
                 for c, lo in enumerate(los)}

    res = {
        "chunked_asarray": _time(lambda: [[jnp.asarray(a) for a in c]
                                          for c in chunked], args.reps),
        "chunked_from_numpy": _time(
            lambda: [b.columns for b in (
                ColumnarBatch.from_numpy(d, schema, v)
                for d, v in host_rows.values())], args.reps),
        "whole_asarray": _time(lambda: [jnp.asarray(a) for a in whole],
                               args.reps),
        "whole_device_put_list": _time(lambda: jax.device_put(whole),
                                       args.reps),
        "whole_list_then_split": _time(
            lambda: split_jit(jax.device_put(whole)), args.reps),
        "body_tail_list_then_split": _time(
            lambda: (split_body_jit(jax.device_put(body)),
                     jax.device_put(tail_host)), args.reps),
        "split_only": _time(lambda: split_jit(on_dev), args.reps),
    }
    # what the split's time is made of
    k = len(los) - 1

    def tiny(arrs):          # as many outputs, next to nothing copied
        return [[a[lo:lo + 8] for a in arrs] for lo in los]

    def stacked(arrs):       # as many bytes, one output an array
        return [a[:k * chunk].reshape(k, chunk) + 0 for a in arrs]

    tiny_jit, stacked_jit = jax.jit(tiny), jax.jit(stacked)
    res.update({
        "split_tiny_outputs": _time(lambda: tiny_jit(on_dev), args.reps),
        "split_stacked": _time(lambda: stacked_jit(on_dev), args.reps),
    })
    return {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "rows": n, "chunks": len(los), "arrays": len(whole),
        "device_bytes_whole": nbytes, "tail_rows": tail,
        "tail_capacity": tail_cap,
        "host_convert_whole_ms": convert_ms,
        "f32_shadow_whole_ms": shadow_ms,
        "split_compile_s": split_compile_s, "split_equal": bool(same),
        "contiguous": [bool(a.flags.c_contiguous) for a in whole],
        "ms": res,
    }


if __name__ == "__main__":
    sys.exit(main())
