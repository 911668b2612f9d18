"""String upload probe: where a hot TPC-H q1's `accelerate()` spends its
host time, and what a string column's arrays cost to send, on the
attached device (PERF.md, PR 34: the readings before and after the
Arrow hand-over).

Part 1 profiles `--reps` hot `accelerate()`s of q1 over the benchmark's
own lineitem (cProfile; the upload is eager, so `accelerate()` is the
whole of it) and prints the functions that own the time, then the
`exec:upload-strings` spans of one more `accelerate()` of each of
`--span-queries` (`transfers`, `per_value`).  Part 2 times
the transfers alone: one chunk's `u8[chunk, 8]` matrix sent 2-D and
flat, a whole run's matrix 2-D and flat with what each leaves on the
device (`bytes_in_use`), and a jitted cut of the flat run into chunks.

    chiprun --chips 1 -- python scripts/string_upload_probe.py --scale 6000000
"""
from __future__ import annotations

import argparse
import cProfile
import io
import json
import os
import pstats
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ms(fn, reps: int) -> dict:
    import jax
    jax.block_until_ready(fn())
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        took.append((time.perf_counter() - t0) * 1e3)
    return {"median_ms": statistics.median(took), "min_ms": min(took)}


def _accelerates(args) -> dict:
    from benchmark.gen import tpch as G
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.models.tpch_data import sources
    from spark_rapids_tpu.models.tpch_queries import QUERIES
    from spark_rapids_tpu.plan.overrides import accelerate
    from spark_rapids_tpu.utils import profile as P
    tables = G.generate(args.seed, args.scale,
                        tables=("lineitem", "orders", "customer"))
    src = sources(tables, args.partitions)
    settings = {"spark.rapids.sql.variableFloatAgg.enabled": True,
                "spark.rapids.sql.incompatibleOps.enabled": True}
    conf = C.RapidsConf(settings)

    def once():
        t0 = time.perf_counter()
        accelerate(QUERIES[1](src, None), conf)
        return (time.perf_counter() - t0) * 1e3

    warm = [once() for _ in range(2)]
    pr = cProfile.Profile()
    pr.enable()
    hot = [once() for _ in range(args.reps)]
    pr.disable()
    text = io.StringIO()
    st = pstats.Stats(pr, stream=text)
    st.sort_stats("tottime").print_stats(22)
    st.sort_stats("cumulative").print_stats(
        "transitions|vector|batch.py|overrides.py:.*(_conv_source)")
    traced = C.RapidsConf(dict(
        settings, **{"spark.rapids.sql.profile.enabled": True}))
    spans = {}
    for q in args.span_queries:
        plan = accelerate(QUERIES[q](src, None), traced)
        spans[f"q{q}"] = [
            dict(s.args, ms=s.dur_ns / 1e6) for s in plan._plan_phase.spans()
            if s.name == P.SPAN_UPLOAD_STRINGS]
    return {"warm_ms": warm, "profiled_ms": hot, "upload_strings": spans,
            "cprofile": text.getvalue()}


def _transfers(args) -> dict:
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    chunk, chunks, cc = args.chunk, args.run_chunks, 8
    rng = np.random.default_rng(args.seed)
    one = np.zeros((chunk, cc), np.uint8)
    one[:, 0] = rng.integers(65, 90, chunk)
    run = np.ascontiguousarray(np.tile(one, (chunks, 1)))
    valid, lens = np.ones(chunk, bool), np.ones(chunk, np.int32)

    def in_use():
        return (dev.memory_stats() or {}).get("bytes_in_use", 0)

    out = {"chunk": chunk, "run_chunks": chunks, "run_bytes": run.nbytes}
    out["chunk_2d"] = _ms(lambda: jnp.asarray(one), args.reps)
    out["chunk_flat"] = _ms(lambda: jnp.asarray(one.reshape(-1)), args.reps)
    out["chunk_three_asarray"] = _ms(
        lambda: [jnp.asarray(one), jnp.asarray(valid), jnp.asarray(lens)],
        args.reps)
    out["chunk_three_one_put"] = _ms(
        lambda: jax.device_put([one, valid, lens]), args.reps)
    out["run_2d"] = _ms(lambda: jax.device_put(run), args.reps)
    out["run_flat"] = _ms(lambda: jax.device_put(run.reshape(-1)), args.reps)
    base = in_use()
    held = jax.block_until_ready(jax.device_put(one))
    out["chunk_2d_device_bytes"] = in_use() - base
    del held
    base = in_use()
    held = jax.block_until_ready(jax.device_put(run))
    out["run_2d_device_bytes"] = in_use() - base
    del held
    base = in_use()
    flat = jax.block_until_ready(jax.device_put(run.reshape(-1)))
    out["run_flat_device_bytes"] = in_use() - base

    def cut(a):
        m = a.reshape(-1, cc)
        return [m[lo:lo + chunk] for lo in range(0, m.shape[0], chunk)]

    t0 = time.perf_counter()
    cut_jit = jax.jit(cut)
    got = jax.block_until_ready(cut_jit(flat))
    out["cut_first_call_s"] = time.perf_counter() - t0
    out["cut_equal"] = bool(np.array_equal(np.asarray(got[-1]), one))
    base = in_use()
    out["cut"] = _ms(lambda: cut_jit(flat), args.reps)
    got = jax.block_until_ready(cut_jit(flat))
    out["cut_outputs_device_bytes"] = in_use() - base
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=6_000_000)
    ap.add_argument("--partitions", type=int, default=2)
    ap.add_argument("--chunk", type=int, default=65_536)
    ap.add_argument("--run-chunks", type=int, default=45)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3400000001)
    ap.add_argument("--span-queries", type=lambda s: [
        int(q) for q in s.split(",")], default=[1, 3, 6])
    ap.add_argument("--tag", default="string_upload_probe")
    args = ap.parse_args()

    import jax
    import spark_rapids_tpu  # noqa: F401  x64 on, compile cache
    d = jax.devices()[0]
    out = {"device": {"platform": d.platform, "kind": d.device_kind},
           "transfers": _transfers(args), "accelerate": _accelerates(args)}
    text = out["accelerate"].pop("cprofile")
    print(text)
    print(json.dumps(out, indent=1))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/{args.tag}.json", "w") as f:
        json.dump(dict(out, cprofile=text), f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
