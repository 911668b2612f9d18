#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the planner path still starts
on the chip.

Drives TPC-H through the entry points a user calls,
`collect(accelerate(plan, conf), conf)`, on ONE TPU chip at SF0.25
(`gen_tables(scale=1_500_000)`, dbgen ratios; `--scale 6000000` is SF1,
see DEFAULT_SCALE), with `spark.rapids.sql.test.enabled` so a plan node that falls back to the
pandas interpreter fails the run, and checks every answer against the
independent reference (`run_query(engine="cpu")`, plan/cpu_eval.py)
with the parity tests' tolerance.  One process, the only one that
touches JAX.  Nothing is caught and carried on from: any phase that
fails ends the run with a traceback and a non-zero exit code.

    python chip_smoke.py                 # one chip: q6, q1, q3
    python chip_smoke.py --queries 6,1,3,5,18
    python chip_smoke.py --chips 4       # ONLY the mesh-exchange query
                                         # and its one-device comparison

The last line of stdout is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Times printed on earlier lines are one smoke run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: TPC-H SF1 in this generator's unit (lineitem rows; BASELINE
#: milestone 2's size)
SF1_SCALE = 6_000_000
#: what the script runs by default.  SF1 ran on the v5e and matched
#: (PERF.md, PR 25) but took 1014 s cold on an empty compile cache, 718 s
#: of it XLA compiles on the chip's shared host: too close to the
#: script's 1200 s limit to be the default.  Scale only is cut; shapes,
#: ratios, queries and conf are SF1's.
DEFAULT_SCALE = 1_500_000
DEFAULT_QUERIES = (6, 1, 3)
#: the --chips 4 lane: aggregate-after-exchange, one partition per chip,
#: at SF0.1 — four chips are charged four times a second, and what the
#: lane shows (shards on every chip, equal answers) does not grow with
#: scale
MESH_QUERY = 3
MESH_SCALE = 600_000
#: tests/parity.py defaults -- the variableFloatAgg tolerance the parity
#: suites hold every workload to
RTOL, ATOL = 1e-5, 1e-6


def smoke_conf(extra: dict | None = None):
    """BENCH_CONF plus test.enabled: every plan node on the device, or
    `accelerate` raises."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF
    settings = dict(BENCH_CONF)
    settings["spark.rapids.sql.test.enabled"] = True
    settings.update(extra or {})
    return C.RapidsConf(settings)


class _CompileCounter:
    """XLA compile requests and persistent-cache hits, from JAX's own
    monitoring events (a request that is not a hit compiled)."""

    def __init__(self):
        import jax.monitoring
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.requests, self.hits


def _compare(expected, got, label: str) -> None:
    sys.path.insert(0, os.path.join(HERE, "tests"))
    try:
        from parity import compare_frames
    finally:
        sys.path.pop(0)
    compare_frames(expected, got, label, rtol=RTOL, atol=ATOL)


def run_and_check(tables, queries, conf=None, num_partitions: int = 2,
                  hot_runs: int = 1, counter=None, out=print) -> list:
    """Run each query cold then hot through accelerate()+collect(),
    and compare the hot answer AND the cold answer with the CPU
    reference.  Raises on a CPU island, a failed compile or a
    mismatch.  Returns one stats dict per query."""
    from spark_rapids_tpu.exec.base import kernel_cache_compiles
    from spark_rapids_tpu.models.tpch_bench import run_query
    from spark_rapids_tpu.ops.pallas_kernels import mosaic_trace_counts
    from spark_rapids_tpu.utils import checks as CK

    conf = conf or smoke_conf()
    stats = []
    for n in queries:
        mosaic0 = mosaic_trace_counts()
        builds0 = kernel_cache_compiles()
        xla0 = counter.snapshot() if counter else (0, 0)
        t0 = time.perf_counter()
        cold = run_query(n, tables, engine="tpu", conf=conf,
                         num_partitions=num_partitions)
        cold_s = time.perf_counter() - t0
        builds1 = kernel_cache_compiles()
        xla1 = counter.snapshot() if counter else (0, 0)

        hot_s, hot = [], cold
        syncs0 = CK.host_sync_count()
        for _ in range(hot_runs):
            t0 = time.perf_counter()
            hot = run_query(n, tables, engine="tpu", conf=conf,
                            num_partitions=num_partitions)
            hot_s.append(time.perf_counter() - t0)
        syncs = (CK.host_sync_count() - syncs0) // max(hot_runs, 1)
        builds2 = kernel_cache_compiles()

        t0 = time.perf_counter()
        expected = run_query(n, tables, engine="cpu",
                             num_partitions=num_partitions)
        ref_s = time.perf_counter() - t0
        if len(expected) == 0:
            raise AssertionError(f"q{n}: reference result is empty")
        _compare(expected, cold, f"q{n} cold")
        _compare(expected, hot, f"q{n} hot")

        mosaic1 = mosaic_trace_counts()
        mosaic = {k: v - mosaic0.get(k, 0) for k, v in mosaic1.items()
                  if v - mosaic0.get(k, 0)}
        s = {"query": n, "rows_out": int(len(hot)),
             "cold_s": cold_s, "hot_s": min(hot_s) if hot_s else None,
             "cpu_reference_s": ref_s,
             "kernel_builds_cold": builds1 - builds0,
             "kernel_builds_hot": builds2 - builds1,
             "xla_compile_requests_cold": xla1[0] - xla0[0],
             "xla_cache_hits_cold": xla1[1] - xla0[1],
             "host_syncs_per_hot_run": syncs,
             "mosaic_kernels_traced": mosaic,
             "matches_cpu_reference": True}
        out(json.dumps({"smoke_query": s}))
        stats.append(s)
    return stats


def _device_block() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _require_tpu(count: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r} "
            f"({devs[0].device_kind}); nothing was run")
    if len(devs) < count:
        raise SystemExit(
            f"chip_smoke: needs {count} chips, JAX found {len(devs)}")


def _gen(seed: int, scale: int):
    import numpy as np
    from spark_rapids_tpu.models.tpch_data import gen_tables
    t0 = time.perf_counter()
    tables = gen_tables(np.random.default_rng(seed), scale)
    print(json.dumps({"data": {
        "generator": "models/tpch_data.gen_tables", "seed": seed,
        "scale": scale, "sf1_scale": SF1_SCALE,
        "lineitem_rows": int(len(tables["lineitem"])),
        "orders_rows": int(len(tables["orders"])),
        "gen_s": time.perf_counter() - t0}}), flush=True)
    return tables


def _assert_on_chip(stats: list) -> None:
    """After the queries: the engine's device is the TPU, its HBM size
    is the chip's own report, and the Mosaic lane really ran where a
    query routes to it."""
    import jax
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    from spark_rapids_tpu.ops.pallas_kernels import mosaic_trace_counts
    dm = DeviceManager.get()
    assert dm.device.platform == "tpu", dm.device
    assert dm.hbm_total_source == "memory_stats", dm.hbm_total_source
    ms = dm.device.memory_stats()
    mosaic = mosaic_trace_counts()
    print(json.dumps({"device_manager": {
        "device": str(dm.device), "hbm_total": dm.hbm_total,
        "hbm_total_source": dm.hbm_total_source,
        "hbm_budget": dm.budget,
        "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
        "mosaic_kernels_traced": mosaic}}))
    assert mosaic, ("no Mosaic kernel was dispatched by any smoke query: "
                    f"{[s['mosaic_kernels_traced'] for s in stats]}")
    assert jax.default_backend() == "tpu"


def _start(args, **what) -> dict:
    """Refuse to run without the chips, then import the package (x64 +
    compile cache dir), say what will run, and make the data."""
    _require_tpu(args.chips)
    import spark_rapids_tpu  # noqa: F401
    import jax
    print(json.dumps({"start": dict(
        device=_device_block(), jax=jax.__version__,
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        scale=args.scale, **what)}), flush=True)
    return _gen(args.seed, args.scale)


def main_one_chip(args) -> None:
    tables = _start(args, queries=args.queries)
    counter = _CompileCounter()
    stats = run_and_check(tables, args.queries, counter=counter,
                          hot_runs=args.hot_runs,
                          out=lambda s: print(s, flush=True))
    _assert_on_chip(stats)


def run_mesh_exchange(tables, n_chips: int, query: int = MESH_QUERY,
                      out=print) -> dict:
    """The cross-chip shuffle lane and what it is compared with: the
    query with one partition per chip under an active n-chip mesh (hash
    exchanges take the mesh collective lane), then the same query on
    one device with no mesh, then the CPU reference.  Asserts every
    mesh device held a shard of an exchange's output, that n distinct
    chips hold the partitions after each exchange (the profiled hot
    run's `exec:exchange-read` spans), that the joins' spans name n
    devices, and that batches changed chips outside the collective only
    in counted moves."""
    from spark_rapids_tpu.models.tpch_bench import run_query
    from spark_rapids_tpu.parallel import mesh as M
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec as X
    from spark_rapids_tpu.utils import checks as CK
    from spark_rapids_tpu.utils import profile as P

    def timed(conf):
        t0 = time.perf_counter()
        df = run_query(query, tables, engine="tpu", conf=conf,
                       num_partitions=n_chips)
        return df, time.perf_counter() - t0

    conf = smoke_conf({"spark.rapids.shuffle.meshExchange.enabled": True})
    mesh = M.make_mesh(n_chips)  # raises when fewer devices are visible
    X._MESH_EXCHANGES_RUN = 0
    X._MESH_SHARD_DEVICES = []
    with M.active_mesh(mesh):
        sharded_cold, cold_s = timed(conf)
        sharded, hot_s = timed(conf)
        n_exchanges, shard_devices = (X._MESH_EXCHANGES_RUN,
                                      list(X._MESH_SHARD_DEVICES))
        # once more, profiled: where the partitions lay
        P.clear_history()
        CK.reset_cross_chip_moves()
        timed(smoke_conf({
            "spark.rapids.shuffle.meshExchange.enabled": True,
            "spark.rapids.sql.profile.enabled": True}))
    spans = P.last_profile().spans
    read_devices = sorted({s.args["device"] for s in spans
                           if s.name == P.SPAN_EXCHANGE_READ})
    join_devices = sorted({s.args.get("device") for s in spans
                           if s.name in (P.SPAN_JOIN_BUILD,
                                         P.SPAN_JOIN_PROBE)})
    moves = CK.cross_chip_move_sites()

    _, single_cold_s = timed(smoke_conf())
    single, single_hot_s = timed(smoke_conf())
    expected = run_query(query, tables, engine="cpu",
                         num_partitions=n_chips)
    _compare(expected, sharded_cold, f"q{query} mesh cold")
    _compare(expected, sharded, f"q{query} mesh")
    _compare(expected, single, f"q{query} one device")
    _compare(single, sharded, f"q{query} mesh vs one device")

    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    s = {"query": query, "chips": n_chips, "mesh_devices": mesh_ids,
         "mesh_cold_s": cold_s, "mesh_hot_s": hot_s,
         "one_device_cold_s": single_cold_s,
         "one_device_hot_s": single_hot_s,
         "mesh_exchanges": n_exchanges,
         "devices_holding_shards_per_exchange": shard_devices,
         "devices_holding_partitions_after_exchange": read_devices,
         "devices_named_by_join_spans": join_devices,
         "cross_chip_moves": {k: list(v) for k, v in moves.items()},
         "matches_cpu_reference": True, "matches_one_device": True}
    out(json.dumps({"mesh_exchange": s}))
    assert n_exchanges > 0, "no hash exchange took the mesh lane"
    for ids in shard_devices:
        assert ids == mesh_ids, (
            f"an exchange's output lived on devices {ids}, the mesh "
            f"is {mesh_ids}")
    assert read_devices == mesh_ids, (
        f"after the exchange the partitions lay on {read_devices}, "
        f"the mesh is {mesh_ids}")
    assert join_devices == mesh_ids, (
        f"the joins ran on {join_devices}, the mesh is {mesh_ids}")
    # the query's one single-partition point: the top-n merge
    assert set(moves) <= {"topn-merge"} and sum(
        n for n, _ in moves.values()) <= 1, moves
    return s


def main_mesh(args) -> None:
    tables = _start(args, mesh_query=MESH_QUERY)
    run_mesh_exchange(tables, args.chips,
                      out=lambda s: print(s, flush=True))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--queries", type=lambda s: [int(x) for x in
                                                 s.split(",")],
                    default=list(DEFAULT_QUERIES),
                    help="TPC-H query numbers (default 6,1,3)")
    ap.add_argument("--scale", type=int, default=None,
                    help="lineitem rows (default: 1,500,000 = SF0.25 on "
                         "one chip, 600,000 with --chips 4; SF1 is "
                         "6,000,000)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hot-runs", type=int, default=1)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the mesh-exchange query and its "
                         "one-device comparison")
    args = ap.parse_args(argv)
    if args.scale is None:
        args.scale = DEFAULT_SCALE if args.chips == 1 else MESH_SCALE
    if args.chips == 1:
        main_one_chip(args)
    else:
        main_mesh(args)
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": _device_block()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
