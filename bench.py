"""Benchmark driver: BASELINE.md milestone configs on the TPU engine.

Mirrors the reference bench harness shape (cold + hot runs,
`TpcxbbLikeBench.scala:26-40`).  Metrics:

  1. tpch_q1_stream  — TPC-H Q1 kernel, PIPELINED dispatches: B
     device-resident batches dispatched back-to-back, synced once (the
     per-task batch-iterator operating mode; `mode: "pipelined"` — the
     per-dispatch sync cost is amortized, and the JSON says so).
  2. tpch_q1_fused   — the same Q1 over B batches vmapped into ONE
     dispatch (device-side batch loop): the HBM-utilization number —
     per-dispatch runtime overhead is paid once per B batches, so the
     wall clock approaches the memory-bound roofline.  Reports
     effective GB/s and fraction of a v5e's ~819 GB/s.
  3. groupby_sf1     — BASELINE milestone 2: group-by sum/count on a
     TPC-H SF1-sized lineitem through the REAL exec path with the
     planner-automatic dictGroupby fast lane (accelerate()'d plan,
     kernel cache, coalesce, metrics); groupby_sf1_sort records the
     general sort-based lane.
  4. join_sort_q3    — milestone 3: dense direct-address join + full
     sort + limit 10 (real q3 tail); join_topn_q3 is the same query
     through the planner's TakeOrderedAndProject lowering (the plan
     shape Spark itself produces).
  5. exchange_mgr    — milestone 4 (single-executor form): hash exchange
     routed through TpuShuffleManager's spillable catalog.
  6. groupby_dict_kernel — the bare Pallas dictionary grouped-sum
     kernel on milestone 2's shape (`mode: "kernel"`).
  7. udf_q27         — milestone 5: TPCx-BB q27 with its text UDF
     compiled by the udf-compiler and run on TPU.

Every hot dispatch gets distinct inputs and is fenced by a D2H
readback, so no caching layer can fake numbers.

`vs_baseline` is the speedup over single-thread pandas running the
identical operation on this host — the reference publishes charts, not
numbers (BASELINE.md), so the CPU-on-same-host ratio is the honest
stand-in for its GPU-vs-CPU-Spark comparisons.

Prints one JSON line per metric, then the driver-facing summary line
LAST: the headline metric plus a `submetrics` list carrying everything.
"""
import json
import time

import numpy as np



def _nominal_hbm_gbps():
    """Nominal HBM GB/s of the device JAX reports, from the one peak
    table (utils/roofline.DEVICE_PEAKS, keyed by device_kind); None on
    a device that is not in it."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.utils import roofline
    return roofline.hbm_gbps(C.RapidsConf())


def _share(gbps, peak):
    return None if peak is None else round(gbps / peak, 4)


#: probed HBM read ceiling, set by main() so later benches (movement
#: ledger roofline) can report utilization against measured hardware
_HBM_PROBE_GBPS = [None]

Q1_ROWS = 1 << 24    # 16.8M rows/batch, 7 x int32/f32 cols = 470MB
Q1_BATCHES = 6
Q1_CYCLES = 8
FUSE_CYCLES = 6

# SPARK_RAPIDS_BENCH_FAST=1: shrink the q1 family's shapes so a
# wall-clock-bounded box records a COMPLETE round (every metric + a
# final parseable summary) instead of dying inside bench_q1_fused —
# the full-size q1 family alone can outrun a driver's window.  The
# JSON stays honest: affected metrics carry "shape": "fast".
import os as _os

BENCH_FAST = bool(_os.environ.get("SPARK_RAPIDS_BENCH_FAST"))
if BENCH_FAST:
    Q1_ROWS = 1 << 21
    Q1_BATCHES = 3
    Q1_CYCLES = 3
    FUSE_CYCLES = 2

FUSE_B = Q1_BATCHES  # fused metric reuses the stream batches (no second
                     # multi-GB host upload)


def _args_of(batch):
    return (
        batch.column("l_returnflag").data,
        batch.column("l_linestatus").data,
        batch.column("l_quantity").data,
        batch.column("l_extendedprice").data,
        batch.column("l_discount").data,
        batch.column("l_tax").data,
        batch.column("l_shipdate").data,
    )


def _check_q1(out, df):
    """All six aggregate columns vs pandas (not just counts + one sum)."""
    from spark_rapids_tpu.models.tpch import q1_reference_pandas
    exp = q1_reference_pandas(df)
    got = {k: np.asarray(out[i], np.float64)
           for i, k in ((2, "sum_qty"), (3, "sum_base_price"),
                        (4, "sum_disc_price"), (5, "sum_charge"),
                        (6, "sum_disc"))}
    got_cnt = np.asarray(out[7])
    exp_rows = {(int(r["l_returnflag"]), int(r["l_linestatus"])): r
                for _, r in exp.iterrows()}
    for g in range(6):
        row = exp_rows.get((g // 2, g % 2))
        exp_cnt = int(row["count_order"]) if row is not None else 0
        assert got_cnt[g] == exp_cnt, \
            f"group {g}: count {got_cnt[g]} != {exp_cnt}"
        if row is None:
            continue
        exp_vals = {
            "sum_qty": row["sum_qty"],
            "sum_base_price": row["sum_base_price"],
            "sum_disc_price": row["sum_disc_price"],
            "sum_charge": row["sum_charge"],
            "sum_disc": row["avg_disc"] * row["count_order"],
        }
        for k, e in exp_vals.items():
            rel = abs(got[k][g] - e) / max(abs(e), 1.0)
            assert rel < 1e-4, f"group {g} {k}: rel err {rel:.2e}"


def bench_q1_stream():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.models.tpch import build_q1_kernel, gen_lineitem

    rng = np.random.default_rng(42)
    batches = [gen_lineitem(rng, Q1_ROWS) for _ in range(Q1_BATCHES)]
    cap = batches[0].capacity
    fn = jax.jit(build_q1_kernel(cap))

    out = fn(*_args_of(batches[0]), jnp.int32(batches[0].num_rows))
    jax.block_until_ready(out)
    df = batches[0].to_pandas()
    _check_q1(out, df)

    warm = [fn(*_args_of(b), jnp.int32(b.num_rows)) for b in batches]
    jax.block_until_ready(warm)
    np.asarray(warm[-1][7])

    total_rows = 0
    t0 = time.perf_counter()
    outs = []
    for c in range(Q1_CYCLES):
        for b in batches:
            n = b.num_rows - (c + 1)
            outs.append(fn(*_args_of(b), jnp.int32(n)))
            total_rows += n
    jax.block_until_ready(outs)
    np.asarray(outs[-1][7])
    tpu_time = time.perf_counter() - t0
    per_query = tpu_time / (Q1_BATCHES * Q1_CYCLES)

    # synchronous single-dispatch time, reported alongside the pipelined
    # number (the baseline is fully synchronous; ADVICE r1)
    t0 = time.perf_counter()
    o = fn(*_args_of(batches[0]), jnp.int32(batches[0].num_rows - 99))
    np.asarray(o[7])
    sync_time = time.perf_counter() - t0

    from spark_rapids_tpu.models.tpch import q1_reference_pandas
    # best-of like every other bench: a single pandas measurement on a
    # busy host swung vs_baseline 4x between rounds
    pandas_time = _best_of(lambda: q1_reference_pandas(df), 2)

    bytes_q = sum(int(a.size) * a.dtype.itemsize
                  for a in _args_of(batches[0]))
    return {
        "metric": "tpch_q1_rows_per_sec", "mode": "pipelined",
        "value": round(total_rows / tpu_time, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / per_query, 2),
        "sync_per_query_ms": round(sync_time * 1e3, 2),
        "pipelined_per_query_ms": round(per_query * 1e3, 2),
        "effective_gbps": round(bytes_q / per_query / 1e9, 1),
        **({"shape": "fast"} if BENCH_FAST else {}),
    }, pandas_time, batches


def bench_q1_fused(pandas_time, batches):
    """Device-side batch loop: the Pallas Q1 kernel over FUSE_B batches
    stacked into ONE dispatch — per-dispatch runtime overhead amortizes
    and the single-HBM-pass kernel approaches the platform's measured
    bandwidth ceiling (`platform_ceiling_gbps`, probed below with a bare
    fused 7-column sum; utilization is reported against BOTH it and
    the device kind's nominal HBM bandwidth)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.models.tpch import build_q1_fused_kernel

    cap = Q1_ROWS * FUSE_B
    # concatenate the stream batches device-side: no new host upload
    flat = [jnp.concatenate(a) for a in zip(*(_args_of(b)
                                              for b in batches))]
    bytes_per_dispatch = sum(int(a.size) * a.dtype.itemsize
                             for a in flat)

    # platform bandwidth ceiling probe: a bare fused multi-column sum
    def probe(salt, *cs):
        return jnp.stack([(c + salt).sum() for c in
                          (cs[2], cs[3], cs[4], cs[5])])
    jp = jax.jit(probe)
    o = jp(jnp.float32(0), *flat)
    jax.block_until_ready(o)
    np.asarray(o)
    t0 = time.perf_counter()
    outs = [jp(jnp.float32(i + 1), *flat) for i in range(4)]
    jax.block_until_ready(outs)
    np.asarray(outs[-1])
    probe_bytes = sum(flat[i].nbytes for i in (2, 3, 4, 5))
    ceiling_gbps = probe_bytes / ((time.perf_counter() - t0) / 4) / 1e9

    # the kernel docstring's 2060 Mrows/s claim is the EIGHT-batch
    # stacked config; reproduce it alongside the 6-batch one by reusing
    # two stream batches (same bytes, no extra multi-GB upload —
    # per-cycle num_rows salts keep dispatches distinct)
    flat8 = [jnp.concatenate([a, a[: 2 * Q1_ROWS]]) for a in flat]
    step8 = build_q1_fused_kernel(Q1_ROWS * 8, Q1_ROWS)
    nums8 = jnp.full((8,), Q1_ROWS, jnp.int32)
    o8 = step8(*flat8, nums8)
    jax.block_until_ready(o8)
    t0 = time.perf_counter()
    outs8 = [step8(*flat8, nums8 - (c + 1)) for c in range(FUSE_CYCLES)]
    jax.block_until_ready(outs8)
    np.asarray(outs8[-1])
    t8 = (time.perf_counter() - t0) / FUSE_CYCLES
    rows8 = 8 * Q1_ROWS / t8
    del flat8, o8, outs8

    step = build_q1_fused_kernel(cap, Q1_ROWS)

    def fn(nums):
        return step(*flat, nums)

    nums0 = jnp.full((FUSE_B,), Q1_ROWS, jnp.int32)
    out = fn(nums0)
    jax.block_until_ready(out)
    # correctness: the fused (8,6) table must equal the per-batch XLA
    # kernel's combined outputs (checked vs pandas in bench_q1_stream)
    from spark_rapids_tpu.models.tpch import build_q1_kernel
    single = jax.jit(build_q1_kernel(Q1_ROWS))
    exp = np.zeros((8, 6))
    for b in batches:
        o = single(*_args_of(b), jnp.int32(b.num_rows))
        for j in range(5):
            exp[:, j] += np.asarray(o[2 + j])
        exp[:, 5] += np.asarray(o[7])
    np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-5)

    t0 = time.perf_counter()
    outs = [fn(nums0 - (c + 1)) for c in range(FUSE_CYCLES)]
    jax.block_until_ready(outs)
    np.asarray(outs[-1])
    tpu_time = time.perf_counter() - t0
    per_dispatch = tpu_time / FUSE_CYCLES
    rows_per_sec = FUSE_B * Q1_ROWS * FUSE_CYCLES / tpu_time
    gbps = bytes_per_dispatch / per_dispatch / 1e9
    per_query = per_dispatch / FUSE_B

    return {
        "metric": "tpch_q1_fused_rows_per_sec", "mode": "fused-batch",
        "value": round(rows_per_sec, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / per_query, 2),
        "effective_gbps": round(gbps, 1),
        "platform_ceiling_gbps": round(ceiling_gbps, 1),
        "ceiling_utilization": round(gbps / ceiling_gbps, 3),
        "nominal_hbm_utilization": _share(gbps, _nominal_hbm_gbps()),
        "stacked8_rows_per_sec": round(rows8, 1),
    }


def bench_q1_engine_fused(pandas_time, batches, fused_batch_value):
    """Whole-stage-fusion acceptance bench (ISSUE 7): TPC-H q1 through
    the REAL engine — filter -> project -> aggregate over the
    device-resident lineitem batches — with
    spark.rapids.sql.fusion.enabled on vs off.  Fusion collapses the
    filter/project chain into the aggregate's update kernel (one XLA
    program per batch, no intermediate ColumnarBatch), so the
    engine-mode number should close at least half the gap to the
    hand-fused batch lane (tpch_q1_fused); `gap_closed` records the
    fraction closed against THIS round's fused-batch value."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.models.tpch import q1_plan
    from spark_rapids_tpu.plan.fusion import fuse_plan

    total_rows = sum(b.num_rows for b in batches)
    base = {"spark.rapids.sql.variableFloatAgg.enabled": True}

    def make_plan(fusion: bool):
        conf = C.RapidsConf(dict(
            base, **{"spark.rapids.sql.fusion.enabled": fusion}))
        # one partition holding every batch: the per-task
        # batch-iterator operating mode, partition-local COMPLETE agg
        plan = q1_plan(LocalBatchSource([list(batches)]))
        with C.session(conf):
            plan = fuse_plan(plan, conf)
        return plan, conf

    results = {}
    frames = {}
    for fusion in (False, True):
        plan, conf = make_plan(fusion)
        with C.session(conf):
            frames[fusion] = plan.to_pandas()  # cold (compile)
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                plan.to_pandas()
                times.append(time.perf_counter() - t0)
        results[fusion] = min(times)
    # bit-exact: fusion must not change a single bit of the result
    import pandas as pd
    pd.testing.assert_frame_equal(
        frames[True].reset_index(drop=True),
        frames[False].reset_index(drop=True))

    best = results[True]
    per_query = best / len(batches)
    value = round(total_rows / best, 1)
    gap_closed = None
    unfused_rows = round(total_rows / results[False], 1)
    if fused_batch_value and fused_batch_value > unfused_rows:
        gap_closed = round((value - unfused_rows)
                           / (fused_batch_value - unfused_rows), 3)
    bytes_q = sum(int(a.size) * a.dtype.itemsize
                  for a in _args_of(batches[0]))
    return {
        "metric": "tpch_q1_engine_fused_rows_per_sec",
        "mode": "engine-fused",
        "value": value, "unit": "rows/s",
        "vs_baseline": round(pandas_time / per_query, 2),
        "unfused_rows_per_sec": unfused_rows,
        "speedup_vs_unfused": round(results[False] / best, 3),
        "fused_batch_rows_per_sec": fused_batch_value,
        "gap_closed_vs_fused_batch": gap_closed,
        "effective_gbps": round(
            bytes_q * len(batches) / best / 1e9, 1),
        "note": "TPC-H q1 through the real exec path "
                "(filter→project→agg fused into one update kernel per "
                "batch via plan/fusion.py) vs the same plan with "
                "fusion.enabled=false; results bit-exact both ways. "
                "gap_closed is (fused_engine - unfused_engine) / "
                "(fused_batch_lane - unfused_engine).",
    }


def probe_hbm_bandwidth() -> float:
    """HBM-RESIDENT device READ bandwidth probe: a fused sum over a
    1GB device-resident f32 array, pipelined and fenced once — what
    the chip's memory system sustains for the read-dominated passes
    these workloads are.  Utilization below is reported against BOTH
    this and the device kind's nominal HBM bandwidth."""
    import jax
    import jax.numpy as jnp
    # 4 x 1GB f32: the read must be GBs to amortize the per-dispatch
    # fixed cost (not measured on the current machine)
    n = 256 << 20
    xs = [jnp.ones((n,), jnp.float32) * (i + 1) for i in range(4)]

    def probe(s, *cs):
        return jnp.stack([(c + s).sum() for c in cs])
    f = jax.jit(probe)
    o = f(jnp.float32(1), *xs)
    jax.block_until_ready(o)
    t0 = time.perf_counter()
    outs = [f(jnp.float32(i + 2), *xs) for i in range(6)]
    jax.block_until_ready(outs)
    np.asarray(outs[-1])
    dt = (time.perf_counter() - t0) / 6
    total = sum(x.nbytes for x in xs)
    del xs
    return total / dt / 1e9


def _best_of(fn, n: int) -> float:
    """min wall-clock of n runs — applied to BOTH engine and pandas
    sides so the vs_baseline ratio is not at the mercy of one cold or
    noisy measurement."""
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _mk_source(dfs, schema=None):
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.plan.transitions import batch_from_df
    from spark_rapids_tpu.plan.nodes import CpuSource
    src = CpuSource.from_pandas(dfs[0]) if schema is None else None
    sch = src.output_schema() if schema is None else schema
    parts = [[batch_from_df(df, sch)] for df in dfs]
    return LocalBatchSource(parts, sch), sch


def bench_groupby():
    """BASELINE milestone 2: HashAggregate group-by sum/count, SF1-size
    lineitem (6M rows), through the real exec path."""
    from spark_rapids_tpu.exprs.aggregates import Count, Sum
    from spark_rapids_tpu.exprs.base import col

    import pandas as pd
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.plan import (CpuAggregate, CpuSource,
                                       accelerate, collect)
    rows, n_keys, n_parts = 3 << 21, 1 << 10, 3  # 6.3M rows total
    rng = np.random.default_rng(5)
    full = pd.DataFrame({
        "k": rng.integers(0, n_keys, rows).astype(np.int64),
        "v": rng.uniform(0, 100, rows),
        "w": rng.uniform(0, 10, rows),
    })
    src = CpuSource.from_pandas(full, num_partitions=n_parts)
    cpu_plan = CpuAggregate(
        [col("k")], [Sum(col("v")).alias("sv"), Sum(col("w")).alias("sw"),
                     Count(col("v")).alias("c")], src)
    # 64K-row batches mean ~100 dispatches — dispatch-bound; the
    # bench operating point uses big batches (the
    # coalesce goal a real cluster would hit).  The DEFAULT conf takes
    # the planner-automatic dictGroupby fast path (fused window +
    # Pallas one-hot grouped sum, f32 accumulation = the variableFloatAgg
    # tolerance the conf opts into); the dict-off variant records the
    # general sort-based path.
    conf = C.RapidsConf(
        {"spark.rapids.sql.variableFloatAgg.enabled": True,
         "spark.rapids.tpu.batchMaxRows": 1 << 22})
    plan = accelerate(cpu_plan, conf)
    got = collect(plan)  # cold + correctness (partial->exchange->final)
    exp = full.groupby("k").agg(sv=("v", "sum"), sw=("w", "sum"),
                                c=("v", "size")).reset_index()
    pandas_time = _best_of(
        lambda: full.groupby("k").agg(sv=("v", "sum"), sw=("w", "sum"),
                                      c=("v", "size")).reset_index(), 3)
    got = got.sort_values("k", ignore_index=True)
    exp = exp.sort_values("k", ignore_index=True)
    assert len(got) == len(exp) and \
        np.allclose(got["sv"].astype(float), exp["sv"], rtol=2e-3) and \
        (got["c"].astype(int).to_numpy() == exp["c"].to_numpy()).all()

    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        collect(plan)
        times.append(time.perf_counter() - t0)
    best = min(times)

    # same plan with the fast paths disabled: the general sort-based
    # lane every non-Sum/Count/Average-shaped aggregation takes
    # (bandedGroupby off too — it would otherwise take this plan)
    sconf = C.RapidsConf(
        {"spark.rapids.sql.variableFloatAgg.enabled": True,
         "spark.rapids.tpu.batchMaxRows": 1 << 22,
         "spark.rapids.tpu.dictGroupby.enabled": False,
         "spark.rapids.tpu.bandedGroupby.enabled": False})
    splan = accelerate(cpu_plan, sconf)
    sgot = collect(splan, sconf)
    sgot = sgot.sort_values("k", ignore_index=True)
    assert len(sgot) == len(exp) and \
        np.allclose(sgot["sv"].astype(float), exp["sv"], rtol=1e-5) and \
        (sgot["c"].astype(int).to_numpy() == exp["c"].to_numpy()).all()
    stimes = []
    for _ in range(3):
        t0 = time.perf_counter()
        collect(splan, sconf)
        stimes.append(time.perf_counter() - t0)
    sbest = min(stimes)

    # banded windowed-MXU lane (dict off): the unbounded-cardinality
    # grouper the engine takes when the key range exceeds the dict
    # budget — variableFloatAgg-class tolerance on the f64 sums
    bconf = C.RapidsConf(
        {"spark.rapids.sql.variableFloatAgg.enabled": True,
         "spark.rapids.tpu.batchMaxRows": 1 << 22,
         "spark.rapids.tpu.dictGroupby.enabled": False})
    bplan = accelerate(cpu_plan, bconf)
    bgot = collect(bplan, bconf).sort_values("k", ignore_index=True)
    assert len(bgot) == len(exp) and \
        np.allclose(bgot["sv"].astype(float), exp["sv"], rtol=2e-3) and \
        (bgot["c"].astype(int).to_numpy() == exp["c"].to_numpy()).all()
    btimes = []
    for _ in range(3):
        t0 = time.perf_counter()
        collect(bplan, bconf)
        btimes.append(time.perf_counter() - t0)
    bbest = min(btimes)
    io_bytes = rows * 24  # k i64 + v f64 + w f64
    return [{
        "metric": "groupby_sf1_rows_per_sec", "mode": "engine",
        "value": round(rows / best, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / best, 2),
        "effective_gbps": round(io_bytes / best / 1e9, 2),
        "note": "DEFAULT conf: planner-automatic dictGroupby fused "
                "window + Pallas one-hot grouped sum; round 4 added "
                "AQE-style small-exchange coalescing (tiny partial "
                "outputs skip the split kernels), memoized check "
                "verification (one flag readback per collect), and "
                "integral Sum support via the f32-exactness "
                "certificate (exact-or-deopt, no conf gate).",
    }, {
        "metric": "groupby_sf1_sort_rows_per_sec", "mode": "engine",
        "value": round(rows / sbest, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / sbest, 2),
        "effective_gbps": round(io_bytes / sbest / 1e9, 2),
        "note": "dict+banded disabled: the general sort-based lane "
                "(bitonic multi-key argsort + batched segmented scans)",
    }, {
        "metric": "groupby_sf1_banded_rows_per_sec", "mode": "engine",
        "value": round(rows / bbest, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / bbest, 2),
        "effective_gbps": round(io_bytes / bbest / 1e9, 2),
        "note": "banded windowed-MXU lane (dict off): sort + per-block "
                "one-hot local tables + one merge matmul; unbounded "
                "group cardinality, exact-or-deopt ints via the "
                "sum(|v|) certificate",
    }]


def bench_join_sort():
    """BASELINE milestone 3: hash join + global sort, the TPC-H q3 shape
    faithfully: q3 ends `ORDER BY revenue DESC ... LIMIT 10`, so the
    engine plan is join -> SortExec (full sort) -> GlobalLimit(10) and
    only the top rows come home (the reference's benchmarked queries
    also collect aggregated/limited outputs, never multi-GB row sets).
    pandas runs the identical merge + full sort + head."""
    import pandas as pd
    from spark_rapids_tpu.exec.joins import HashJoinExec, JoinType
    from spark_rapids_tpu.exec.limit import GlobalLimitExec
    from spark_rapids_tpu.exec.sort import SortExec, desc
    from spark_rapids_tpu.exprs.base import col

    n_li, n_ord = 1 << 22, 1 << 19   # 4.2M lineitem, 524k orders
    rng = np.random.default_rng(9)
    li = pd.DataFrame({
        "l_orderkey": rng.integers(0, n_ord * 2, n_li).astype(np.int64),
        "l_revenue": rng.uniform(1, 1000, n_li),
    })
    orders = pd.DataFrame({
        "o_orderkey": np.arange(n_ord, dtype=np.int64),
        "o_custkey": rng.integers(0, 99999, n_ord).astype(np.int64),
    })
    from spark_rapids_tpu import config as C
    conf = C.RapidsConf({"spark.rapids.tpu.batchMaxRows": 1 << 22})
    lsrc, _ = _mk_source([li])
    osrc, _ = _mk_source([orders])
    plan = GlobalLimitExec(10, SortExec(
        [desc(col("l_revenue"))],
        HashJoinExec(JoinType.INNER, [col("l_orderkey")],
                     [col("o_orderkey")], lsrc, osrc, None)))
    with C.session(conf):
        got = plan.collect().to_pandas()

    def pandas_run():
        return (li.merge(orders, left_on="l_orderkey",
                         right_on="o_orderkey", how="inner")
                .sort_values("l_revenue", ascending=False).head(10))
    exp = pandas_run()
    pandas_time = _best_of(pandas_run, 3)
    assert len(got) == 10
    np.testing.assert_allclose(
        got["l_revenue"].astype(float).to_numpy(),
        exp["l_revenue"].to_numpy(), rtol=1e-6)
    np.testing.assert_array_equal(
        got["o_custkey"].astype(np.int64).to_numpy(),
        exp["o_custkey"].to_numpy())

    def engine_run():
        # to_pandas forces the full async pipeline to the host — the
        # engine is async-until-collect, so a bare collect() would only
        # queue the work
        with C.session(conf):
            plan.collect().to_pandas()
    best = _best_of(engine_run, 3)

    # the plan Spark actually produces for ORDER BY + LIMIT is
    # TakeOrderedAndProject; our planner lowers limit-over-sort to
    # SortedTopNExec (top_k candidate pruning + exact candidate re-sort)
    from spark_rapids_tpu.exec.sort import SortedTopNExec
    tplan = SortedTopNExec(10, [desc(col("l_revenue"))],
                           HashJoinExec(JoinType.INNER, [col("l_orderkey")],
                                        [col("o_orderkey")], lsrc, osrc,
                                        None))
    with C.session(conf):
        tgot = tplan.collect().to_pandas()
    np.testing.assert_allclose(
        tgot["l_revenue"].astype(float).to_numpy(),
        exp["l_revenue"].to_numpy(), rtol=1e-6)

    def topn_run():
        with C.session(conf):
            tplan.collect().to_pandas()
    tbest = _best_of(topn_run, 3)
    jbytes = n_li * 16 + n_ord * 16
    return [{
        "metric": "join_sort_q3_rows_per_sec", "mode": "engine",
        "value": round(n_li / best, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / best, 2),
        "effective_gbps": round(jbytes / best / 1e9, 2),
        "note": "direct-address dense join (round 4: merged "
                "occupancy+index table, packed-validity lookup, "
                "i32-shadow-only payload gathers, equi-key remat from "
                "the probe side) + full sort + limit 10; round 4 also "
                "fused the limit into the sort gather and merged the "
                "packed sort words into one variadic sort network",
    }, {
        "metric": "join_topn_q3_rows_per_sec", "mode": "engine",
        "value": round(n_li / tbest, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / tbest, 2),
        "effective_gbps": round(jbytes / tbest / 1e9, 2),
        "note": "same query through the planner's TakeOrderedAndProject "
                "lowering — the plan shape Spark itself produces for "
                "ORDER BY + LIMIT. Round 4: f32 monotone-downcast "
                "candidate pruning with exact f64 re-rank (64-bit "
                "top_k is ~8x slower than 32-bit on this chip) and the "
                "leaner dense-join probe.",
    }]


def bench_exchange_manager():
    """BASELINE milestone 4 (single-executor form): hash exchange routed
    through the shuffle manager's spillable catalog."""
    import pandas as pd
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning

    rows, n_parts = 1 << 22, 8
    rng = np.random.default_rng(13)
    df = pd.DataFrame({
        "k": rng.integers(0, 1 << 20, rows).astype(np.int64),
        "v": rng.uniform(0, 1, rows),
    })
    src, _ = _mk_source([df])
    conf = C.RapidsConf({"spark.rapids.shuffle.enabled": True})

    def run():
        with C.session(conf):
            ex = ShuffleExchangeExec(
                HashPartitioning([col("k")], n_parts), src)
            total = 0
            for it in ex.execute_partitions():
                for b in it:
                    total += b.num_rows
            return total

    total = run()  # cold
    assert total == rows

    def pandas_run():
        parts = df.groupby(np.asarray(df["k"]) % n_parts, sort=False)
        return [g for _, g in parts]
    pandas_time = _best_of(pandas_run, 3)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    best = min(times)
    return {
        "metric": "exchange_mgr_rows_per_sec", "mode": "engine",
        "value": round(rows / best, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / best, 2),
        "effective_gbps": round(rows * 16 / best / 1e9, 2),
        "note": "one (pid, iota) sort "
                "(partitioning._pid_sort_reorder) + stacked gathers; "
                "i32 murmur3 over the narrow shadow.",
    }


def bench_groupby_dict_kernel():
    """Milestone 2's shape through the Pallas dictionary grouped-sum
    kernel (ops/pallas_kernels.grouped_sum_pallas): keys already ids in
    [0, G) — the sort-free path; f32-accumulator (variableFloatAgg)
    semantics."""
    import jax
    import pandas as pd
    from spark_rapids_tpu.ops.pallas_kernels import grouped_sum_pallas

    rows, n_keys = 1 << 22, 1 << 10
    rng = np.random.default_rng(5)
    keys = rng.integers(0, n_keys, rows).astype(np.int32)
    v = rng.uniform(0, 100, rows).astype(np.float32)
    w = rng.uniform(0, 10, rows).astype(np.float32)
    kd, vd, wd = map(jax.device_put, (keys, v, w))
    sums, counts = grouped_sum_pallas(kd, (vd, wd), rows,
                                      n_groups=n_keys, capacity=rows)
    sums, counts = np.asarray(sums), np.asarray(counts)
    df = pd.DataFrame({"k": keys, "v": v.astype(float),
                       "w": w.astype(float)})
    t0 = time.perf_counter()
    exp = df.groupby("k").agg(sv=("v", "sum"), sw=("w", "sum"),
                              c=("v", "size"))
    pandas_time = time.perf_counter() - t0
    assert (counts == exp["c"].to_numpy()).all()
    np.testing.assert_allclose(sums[:, 0], exp["sv"].to_numpy(),
                               rtol=2e-3)
    t0 = time.perf_counter()
    outs = [grouped_sum_pallas(kd, (vd, wd), rows - i,
                               n_groups=n_keys, capacity=rows)
            for i in range(4)]
    jax.block_until_ready(outs)
    np.asarray(outs[-1][0])
    best = (time.perf_counter() - t0) / 4
    return {
        "metric": "groupby_dict_kernel_rows_per_sec", "mode": "kernel",
        "value": round(rows / best, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / best, 2),
        "effective_gbps": round(rows * 12 / best / 1e9, 2),
        "note": "dictionary-encoded keys (ids in [0,G)); the sort-free "
                "Pallas path the planner adopts next via dictionary "
                "detection; f32-accumulator (variableFloatAgg) semantics",
    }


def bench_spmd_stage():
    """SPMD whole-stage lane (ISSUE 12): the same fused
    project->filter->project stage at 8/32/128 partitions through the
    per-partition lane (one Python dispatch per partition batch) vs
    the SPMD gang lane (ONE jit-with-shardings dispatch over the
    active mesh).  Reports wall clock, Python dispatches per stage —
    the O(partitions) -> O(1) claim, counted from exec.spmd's gang
    counters and by construction for the per-partition lane — and the
    ledger's collective-edge bytes for the gang's implicit cross-shard
    reductions."""
    import jax
    import pandas as pd
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec import spmd as SP
    from spark_rapids_tpu.exec.basic import (FilterExec,
                                             LocalBatchSource,
                                             ProjectExec)
    from spark_rapids_tpu.exprs.base import col, lit
    from spark_rapids_tpu.parallel.mesh import active_mesh, make_mesh
    from spark_rapids_tpu.plan.fusion import fuse_plan
    from spark_rapids_tpu.utils import profile as P

    n_dev = min(8, len(jax.devices()))
    mesh = make_mesh(n_dev)
    rows_per_part = 1 << 13
    base_conf = {"spark.rapids.sql.scheduler.enabled": False}
    confs = {
        "per_partition": C.RapidsConf(dict(base_conf)),
        "spmd": C.RapidsConf({**base_conf,
                              "spark.rapids.sql.spmd.enabled": True}),
    }
    out = []
    for parts in (8, 32, 128):
        rng = np.random.default_rng(parts)
        partitions = []
        for _ in range(parts):
            partitions.append([ColumnarBatch.from_numpy({
                "k": rng.integers(0, 1 << 20,
                                  rows_per_part).astype(np.int64),
                "v": rng.uniform(0, 1, rows_per_part),
            })])
        schema = partitions[0][0].schema

        def build():
            src = LocalBatchSource(partitions, schema)
            return FilterExec(
                col("k") % lit(7) != lit(0),
                ProjectExec([(col("k") * lit(3)).alias("k"),
                             (col("v") + col("v")).alias("v")], src))

        res = {}
        for mode, conf in confs.items():
            with C.session(conf), active_mesh(mesh):
                plan = fuse_plan(build(), conf)
                plan.collect()  # warm compile
                SP.reset_spmd_stats()
                times = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    got = plan.collect()
                    got.num_rows  # fence: sync the output count
                    times.append(time.perf_counter() - t0)
                st = SP.spmd_stats()
                # gang lane: counted dispatches; per-partition lane:
                # one kernel call per partition batch by construction
                disp = (st["gang_dispatches"] // 3 or 1) \
                    if mode == "spmd" else parts
                # one profiled pass for the collective-edge bytes
                pconf = conf.set("spark.rapids.sql.profile.enabled",
                                 True)
                with C.session(pconf):
                    fuse_plan(build(), pconf).collect()
                prof = P.last_profile()
                csites = (prof.movement or {}).get("edges", {}).get(
                    "collective", {}).get("sites", {})
                res[mode] = {
                    "wall_ms": round(min(times) * 1e3, 2),
                    "dispatches_per_stage": disp,
                    "collective_bytes": csites.get(
                        "spmd-stage", {}).get("bytes", 0),
                }
        pp, sp = res["per_partition"], res["spmd"]
        out.append({
            "metric": f"spmd_stage_p{parts}_wall_ms",
            "mode": "spmd-vs-per-partition",
            "value": sp["wall_ms"], "unit": "ms",
            "vs_baseline": round(pp["wall_ms"]
                                 / max(sp["wall_ms"], 1e-9), 2),
            "mesh_devices": n_dev,
            "dispatches_spmd": sp["dispatches_per_stage"],
            "dispatches_per_partition": pp["dispatches_per_stage"],
            "spmd_collective_bytes": sp["collective_bytes"],
            "note": "fused stage over %d partitions x %d rows: SPMD "
                    "gang wall vs per-partition lane wall "
                    "(vs_baseline = per-partition/spmd); dispatches "
                    "per stage is the O(partitions)->O(1) evidence"
                    % (parts, rows_per_part),
        })
    return out


def bench_udf_q27():
    """BASELINE milestone 5: TPCx-BB q27 through the udf-compiler — the
    review-text UDF compiles to the expression AST and runs on TPU
    (the reference's Q27Like THROWS 'uses UDF'; this path exceeds it).

    Operating point: 2M reviews / ~200K items.  The milestone is
    'q27 on SF10K' — the old 262K-row point was engine-fixed-cost
    dominated (r4 note) and unrepresentative of the milestone's scale;
    q27 touches ONLY product_reviews, so the bench generates just that
    table (the full TPC-DS catalog generation it used to pay served
    nothing)."""
    import numpy as np
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exec.base import TpuExec
    from spark_rapids_tpu.models import tpcxbb
    from spark_rapids_tpu.models.data_util import make_sources
    from spark_rapids_tpu.plan import accelerate, collect

    rng = np.random.default_rng(21)
    n_reviews = 1 << 21
    rv = tpcxbb.gen_reviews(rng, n_reviews, n_reviews // 10,
                            n_reviews // 4)
    t = make_sources({"product_reviews": rv},
                     {"product_reviews": tpcxbb.REVIEWS_SCHEMA}, 2)
    conf = C.RapidsConf(
        {"spark.rapids.sql.variableFloatAgg.enabled": True})
    plan = accelerate(tpcxbb.QUERIES["q27"](t, lambda p: None), conf)
    assert isinstance(plan, TpuExec), "q27 UDF fell back to CPU"
    got = collect(plan, conf)
    assert len(got) == 100

    def pandas_run():
        flag = rv["pr_content"].str.contains("quality|value",
                                             regex=True).astype(int)
        g = rv.assign(mention=flag).groupby("pr_item_sk").agg(
            mentions=("mention", "sum"), n_reviews=("mention", "size"),
            avg_rating=("pr_rating", "mean")).reset_index()
        return g[g.mentions > 0].sort_values(
            ["mentions", "pr_item_sk"],
            ascending=[False, True]).head(100)
    exp = pandas_run()
    np.testing.assert_array_equal(
        got["pr_item_sk"].astype(np.int64).to_numpy(),
        exp["pr_item_sk"].to_numpy())
    np.testing.assert_array_equal(
        got["mentions"].astype(np.int64).to_numpy(),
        exp["mentions"].to_numpy())
    pandas_time = _best_of(pandas_run, 3)

    def engine_run():
        collect(plan, conf)
    best = _best_of(engine_run, 3)
    ubytes = int(rv["pr_content"].str.len().sum()) + 16 * n_reviews
    return {
        "metric": "udf_q27_rows_per_sec", "mode": "engine",
        "value": round(n_reviews / best, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / best, 2),
        "effective_gbps": round(ubytes / best / 1e9, 2),
        "note": "TPCx-BB q27 via the udf-compiler (compiled Python "
                "sentiment/extraction UDF on TPU; reference Q27Like "
                "throws 'uses UDF'). Where the time goes (profiled per "
                "plan subtree, round 5): the post-HAVING "
                "CoalesceBatchesExec used to pay 13 count syncs + two "
                "gather rounds (~450ms of the old 945ms) dense-slicing "
                "deferred-selection batches; lazy pass-through removed "
                "it entirely. Remaining ~550ms: compiled-UDF string "
                "kernels ~105ms, 200K-group partial agg ~85ms, "
                "exchange ~100ms, final agg ~130ms, filter+top100 "
                "~70ms, collect boundary ~60ms.",
    }


#: set by bench_profile_overhead; the driver-facing summary line carries
#: it so the observability layer's cost is tracked round-to-round
_PROFILE_OVERHEAD_PCT = [None]
#: set by bench_telemetry_overhead: engine-mode q1/q5 wall-clock cost of
#: the always-on telemetry layer (acceptance budget < 2%)
_TELEMETRY_OVERHEAD_PCT = [None]
#: set by bench_movement_ledger: {edge: [MBytes, effective GB/s]} from a
#: profiled manager-lane q5 — BENCH_r06+ tracks movement trajectory,
#: not just wall clock
_MOVEMENT_SUMMARY = [None]
#: set by bench_kernelprof: sampled-attribution overhead + the
#: kernel-vs-compute coverage ratio + the hottest kernel — BENCH_r08+
#: tracks per-kernel attribution round-to-round
_KERNELPROF_SUMMARY = [None]
#: set by bench_residency_overhead: residency-ledger wall-clock cost +
#: the profiled q5 HBM high-water mark and leak verdict — BENCH_r09+
#: tracks per-lane residency trajectory (down is good)
_RESIDENCY_SUMMARY = [None]
#: set by bench_out_of_core: graceful-degradation trajectory — the
#: slowdown and spill traffic of running a sort whose working set is
#: 2x / 10x the accounted HBM budget — BENCH_r09+ tracks how much the
#: external lanes cost as the budget shrinks (down is good)
_OOCORE_SUMMARY = [None]


def bench_out_of_core():
    """Out-of-core graceful-degradation bench (ISSUE 16): one global
    sort run uncapped, then with `spark.rapids.memory.hbmBudgetBytes`
    at 1/2 and 1/10 of the measured working set — the capped lanes
    degrade to the external merge sort (runs streamed down the
    host->disk spill chain, hierarchical window-sized merges) instead
    of erroring.  Reports wall clock per lane, spilled run MB, and
    merge-pass counts; every capped lane is verified bit-exact against
    the uncapped one, so the numbers are the cost of CORRECT
    degradation, not of a different answer."""
    import tempfile

    import pandas as pd

    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exec.sort import SortExec, asc, desc
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.memory import ResourceEnv
    from spark_rapids_tpu.memory import oocore as OC
    from spark_rapids_tpu.memory import retry as R
    from spark_rapids_tpu.utils import metrics as M

    n = 4_000 if BENCH_FAST else 12_000
    rng = np.random.default_rng(11)
    df = pd.DataFrame({
        "x": rng.integers(-500, 500, n).astype(np.int64),
        "y": rng.integers(0, 10**6, n).astype(np.int64)})
    nb = 8
    step = -(-n // nb)

    def plan():
        return SortExec(
            [asc(col("x")), desc(col("y"))],
            LocalBatchSource([[ColumnarBatch.from_pandas(
                df.iloc[i:i + step].reset_index(drop=True))
                for i in range(0, n, step)]]))

    # working set: the retry lattice's own estimate (2x device bytes)
    working_set = 2 * n * 2 * 8

    def run_lane(cap):
        keys = {C.HBM_ALLOC_FRACTION.key: 1.0, C.HBM_RESERVE.key: 0,
                C.CONCURRENT_TPU_TASKS.key: 1}
        if cap:
            keys[C.HBM_BUDGET_BYTES.key] = int(cap)
        conf = C.RapidsConf(keys)
        C.set_active_conf(conf)
        ResourceEnv.init(hbm_total=1 << 30,
                         spill_dir=tempfile.mkdtemp())
        R.reset_oom_injection()
        OC.reset_run_accounting()
        p = plan()
        with C.session(conf):
            p.collect()  # warm the lane's kernels
        OC.reset_run_accounting()
        p = plan()
        t0 = time.perf_counter()
        with C.session(conf):
            out = p.collect().to_pandas()
        wall = time.perf_counter() - t0

        def tree_metric(node):
            return node.metrics.value(M.NUM_EXTERNAL_MERGE_PASSES) + \
                sum(tree_metric(ch) for ch in node.children)

        passes = int(tree_metric(p))
        spill_mb = OC.run_bytes_spilled() / 1e6
        ResourceEnv.shutdown()
        C.set_active_conf(C.RapidsConf())
        return out, wall, spill_mb, passes

    base, wall_full, _, passes_full = run_lane(0)
    lanes = {}
    for name, cap in (("half", working_set // 2),
                      ("tenth", working_set // 10)):
        out, wall, spill_mb, passes = run_lane(cap)
        pd.testing.assert_frame_equal(
            out.reset_index(drop=True), base.reset_index(drop=True),
            check_exact=True)
        lanes[name] = {"wall_ms": round(wall * 1e3, 1),
                       "spill_mb": round(spill_mb, 3),
                       "merge_passes": passes}
    slowdown = lanes["tenth"]["wall_ms"] / max(wall_full * 1e3, 1e-9)
    _OOCORE_SUMMARY[0] = {
        "tenth_budget_slowdown": round(slowdown, 2),
        "spill_mb_tenth": lanes["tenth"]["spill_mb"],
        "merge_passes_tenth": lanes["tenth"]["merge_passes"]}
    return {
        "metric": "oocore_tenth_budget_slowdown", "value": round(slowdown, 3),
        "unit": "x",
        # not a speed ratio: the uncapped lane is the baseline, and a
        # degradation within ~8x of it for a 10x-over-budget working
        # set counts as full marks on the graceful-degradation budget
        "vs_baseline": round(min(2.0, 8.0 / max(slowdown, 0.1)), 2),
        "rows": n,
        "working_set_bytes": working_set,
        "wall_uncapped_ms": round(wall_full * 1e3, 1),
        "merge_passes_uncapped": passes_full,
        "wall_half_ms": lanes["half"]["wall_ms"],
        "spill_mb_half": lanes["half"]["spill_mb"],
        "merge_passes_half": lanes["half"]["merge_passes"],
        "wall_tenth_ms": lanes["tenth"]["wall_ms"],
        "spill_mb_tenth": lanes["tenth"]["spill_mb"],
        "merge_passes_tenth": lanes["tenth"]["merge_passes"],
        "note": "external sort under hbmBudgetBytes caps; capped lanes "
                "bit-exact vs uncapped",
        **({"shape": "fast"} if BENCH_FAST else {}),
    }


def bench_movement_ledger():
    """Data-movement ledger acceptance bench (ISSUE 8): TPC-H q5
    through the manager shuffle lane (2 in-process executors, seeded
    OOM injection against a shrunk budget so spills are real) with the
    movement ledger on.  Reports per-edge byte totals + effective GB/s
    and the utilization vs the PROBED HBM ceiling, so the slow-lane
    rescues (ROADMAP item 5) land with byte evidence."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.memory import retry as R
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    from spark_rapids_tpu.models.tpch_data import gen_tables
    from spark_rapids_tpu.utils import profile as P

    tables = gen_tables(np.random.default_rng(11), 200_000)
    conf = C.RapidsConf({**BENCH_CONF,
        "spark.rapids.sql.profile.enabled": True,
        "spark.rapids.shuffle.enabled": True,
        "spark.rapids.shuffle.localExecutors": 2,
        "spark.rapids.memory.faultInjection.oomRate": 0.25,
        "spark.rapids.memory.faultInjection.seed": 11,
        "spark.rapids.memory.faultInjection.maxInjections": 8})
    R.reset_oom_injection()
    t0 = time.perf_counter()
    run_query(5, tables, engine="tpu", conf=conf)
    wall = time.perf_counter() - t0
    R.reset_oom_injection()
    prof = P.last_profile()
    mv = prof.movement or {"edges": {}, "total_bytes": 0}
    edges = {}
    for edge, e in mv["edges"].items():
        edges[edge] = [round(e["bytes"] / 1e6, 3), e["gbps_avg"]]
    _MOVEMENT_SUMMARY[0] = edges
    hbm = _HBM_PROBE_GBPS[0] or _nominal_hbm_gbps()
    total = mv["total_bytes"]
    gbps = total / wall / 1e9 if wall > 0 else 0.0
    return {
        "metric": "movement_total_mb", "value": round(total / 1e6, 3),
        "unit": "MB",
        # >= 1.0 means every edge class the lane exercises reported
        "vs_baseline": round(min(1.0, sum(
            1 for e in mv["edges"].values() if e["bytes"]) / 4.0), 2),
        "wall_ms": round(wall * 1e3, 1),
        "effective_gbps": round(gbps, 4),
        "hbm_probe_utilization": round(gbps / hbm, 6),
        "edges": {k: {"mb": v[0], "gbps": v[1]}
                  for k, v in edges.items()},
    }


_TAIL_SUMMARY = [None]


def bench_tail_latency():
    """Tail-tolerance acceptance bench (ISSUE 9): a manager-lane
    exchange with ONE executor delay-injected 10x slower (seeded
    map-task straggler), run repeatedly with speculation+hedging+
    replication OFF vs ON under the same seed.  Reports p50/p95 per
    mode — the ON p95 must sit measurably below OFF, since the
    straggler loses every first-wins race instead of serializing the
    stage — plus the speculation/hedge/replication counters."""
    import pandas as pd

    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exec import speculation as SPEC
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.manager import (MapOutputRegistry,
                                                  TpuShuffleManager)
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    from spark_rapids_tpu.shuffle.recovery import PeerHealth
    from spark_rapids_tpu.utils import watchdog as W

    rng = np.random.default_rng(11)
    df = pd.DataFrame({
        "k": rng.integers(0, 500, 200_000).astype(np.int64),
        "v": rng.integers(0, 10**6, 200_000).astype(np.int64)})
    base = {
        "spark.rapids.shuffle.enabled": True,
        "spark.rapids.shuffle.localExecutors": 3,
        "spark.rapids.sql.watchdog.pollInterval": 0.05,
        "spark.rapids.memory.faultInjection.slowSite": "map-task",
        "spark.rapids.memory.faultInjection.slowFactor": 10.0,
        "spark.rapids.memory.faultInjection.slowUnitMs": 40.0,
        "spark.rapids.memory.faultInjection.slowVictim": "local-1",
        "spark.rapids.memory.faultInjection.slowSeed": 11,
    }
    tail_on = {
        "spark.rapids.sql.speculation.enabled": True,
        "spark.rapids.sql.speculation.minTaskRuntimeMs": 50.0,
        "spark.rapids.sql.speculation.minCompletedTasks": 1,
        "spark.rapids.shuffle.replication.factor": 2,
        "spark.rapids.shuffle.hedge.enabled": True,
        "spark.rapids.shuffle.hedge.delayMs": 60.0,
    }

    def reset():
        MapOutputRegistry.clear()
        PeerHealth.get().clear()
        W.reset_slow_injection()
        for eid in list(TpuShuffleManager._managers):
            TpuShuffleManager._managers[eid].close()

    def run_once(conf):
        reset()
        t0 = time.perf_counter()
        with C.session(conf):
            src = LocalBatchSource.from_pandas(df, num_partitions=4)
            ex = ShuffleExchangeExec(
                HashPartitioning([col("k")], 3), src)
            rows = sum(b.num_rows for it in ex.execute_partitions()
                       for b in it)
        assert rows == len(df), rows
        return (time.perf_counter() - t0) * 1e3, ex.metrics.as_dict()

    REPS = 7
    off_conf = C.RapidsConf(dict(base))
    on_conf = C.RapidsConf({**base, **tail_on})
    lat_off = [run_once(off_conf)[0] for _ in range(REPS)]
    SPEC.reset_speculation_stats()
    on_runs = [run_once(on_conf) for _ in range(REPS)]
    lat_on = [t for t, _ in on_runs]
    reset()
    counters = {"spec_tasks": 0, "spec_wins": 0, "hedged": 0,
                "hedged_wins": 0, "replicated_mb": 0.0}
    for _, m in on_runs:
        counters["spec_tasks"] += int(m.get("numSpeculativeTasks", 0))
        counters["spec_wins"] += int(m.get("numSpeculativeWins", 0))
        counters["hedged"] += int(m.get("numHedgedFetches", 0))
        counters["hedged_wins"] += int(m.get("numHedgedWins", 0))
        counters["replicated_mb"] += m.get("replicatedBytes", 0) / 1e6
    counters["replicated_mb"] = round(counters["replicated_mb"], 2)
    p50_off, p95_off = np.percentile(lat_off, [50, 95])
    p50_on, p95_on = np.percentile(lat_on, [50, 95])
    speedup = p95_off / p95_on if p95_on > 0 else 0.0
    _TAIL_SUMMARY[0] = {"p95_speedup": round(speedup, 3),
                        "spec_wins": counters["spec_wins"],
                        "hedged_wins": counters["hedged_wins"]}
    return {
        "metric": "tail_latency_p95_speedup",
        "value": round(speedup, 3),
        "unit": "x",
        # > 1.0 means the tail layer beat the injected straggler
        "vs_baseline": round(speedup, 3),
        "p50_off_ms": round(p50_off, 1), "p95_off_ms": round(p95_off, 1),
        "p50_on_ms": round(p50_on, 1), "p95_on_ms": round(p95_on, 1),
        **counters,
    }


def bench_profile_overhead():
    """Query-profile acceptance bench (ISSUE 5): TPC-H q1 through the
    engine with spark.rapids.sql.profile.enabled off vs on.  The
    disabled path must be free (no tracer objects on the hot loop);
    the enabled path pays span bookkeeping + metric resolution and its
    overhead must stay under ~2%.  Records the percentage so a
    regression shows as a number, not a mystery slowdown."""
    import jax
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    from spark_rapids_tpu.models.tpch_data import gen_tables
    from spark_rapids_tpu.utils import profile as P

    tables = gen_tables(np.random.default_rng(11), 200_000)
    conf_off = C.RapidsConf(dict(BENCH_CONF))
    conf_on = C.RapidsConf({**BENCH_CONF,
                            "spark.rapids.sql.profile.enabled": True})
    run_query(1, tables, engine="tpu", conf=conf_off)  # warm compile

    def timed(conf, n=3):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            run_query(1, tables, engine="tpu", conf=conf)
            best = min(best, time.perf_counter() - t0)
        return best

    t_off = timed(conf_off)
    t_on = timed(conf_on)
    prof = P.last_profile()
    overhead_pct = round(100.0 * (t_on - t_off) / t_off, 2)
    _PROFILE_OVERHEAD_PCT[0] = overhead_pct
    return {
        "metric": "profile_overhead_pct", "value": overhead_pct,
        "unit": "%",
        # not a speed ratio: >=1.0 means "within the 2% budget"
        "vs_baseline": round(min(2.0, 2.0 / max(overhead_pct, 0.01)), 2)
        if overhead_pct > 0 else 2.0,
        "q1_off_ms": round(t_off * 1e3, 1),
        "q1_on_ms": round(t_on * 1e3, 1),
        "spans": len(prof.spans) if prof else 0,
        "events": len(prof.events) if prof else 0,
        "span_depth": prof.span_depth() if prof else 0,
    }


def bench_kernelprof():
    """Kernel-attribution acceptance bench (ISSUE 13): TPC-H q1 with
    profiling on, first WITHOUT kernel attribution (the baseline),
    then with it sampling every dispatch (sampleRate=1).  Reports (a)
    the attribution overhead — acceptance budget < 2% at the default
    rate, measured here at the worst-case rate of 1 as well — and (b)
    the COVERAGE ratio: the '-- kernels --' section's summed per-kernel
    device time over the wall-clock breakdown's compute category
    (acceptance: within 20%, i.e. ratio in [0.8, 1.2], modulo the
    Python orchestration the compute bucket also absorbs).  Leaves
    attribution disabled afterwards so later benches run raw."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    from spark_rapids_tpu.models.tpch_data import gen_tables
    from spark_rapids_tpu.utils import kernelprof as KP
    from spark_rapids_tpu.utils import profile as P

    tables = gen_tables(np.random.default_rng(11), 200_000)
    # pipelining OFF for the coverage comparison: sampled kernel time
    # is CUMULATIVE across producer threads while the breakdown's
    # compute bucket is the wall-clock residual — only a single-thread
    # run makes "kernel sum vs compute bucket" apples-to-apples
    conf_off = C.RapidsConf({**BENCH_CONF,
        "spark.rapids.sql.pipeline.enabled": False,
        "spark.rapids.sql.profile.enabled": True})
    conf_on = C.RapidsConf({**BENCH_CONF,
        "spark.rapids.sql.pipeline.enabled": False,
        "spark.rapids.sql.profile.enabled": True,
        "spark.rapids.sql.profile.kernels.enabled": True})  # rate 8
    conf_full = C.RapidsConf({**BENCH_CONF,
        "spark.rapids.sql.pipeline.enabled": False,
        "spark.rapids.sql.profile.enabled": True,
        "spark.rapids.sql.profile.kernels.enabled": True,
        "spark.rapids.sql.profile.kernels.sampleRate": 1})
    run_query(1, tables, engine="tpu", conf=conf_off)  # warm compile

    def timed(conf, n=3):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            run_query(1, tables, engine="tpu", conf=conf)
            best = min(best, time.perf_counter() - t0)
        return best

    try:
        t_off = timed(conf_off)
        # overhead is judged at the DEFAULT sample rate (the <2%
        # budget); the coverage run then samples every dispatch so the
        # kernel sum is directly comparable to the compute bucket
        t_on = timed(conf_on)
        run_query(1, tables, engine="tpu", conf=conf_full)
        prof = P.last_profile()
        rows = prof.kernels or []
        kernel_ms = sum(r["device_ms"] for r in rows)
        compute_ms = prof.breakdown.get("compute_s", 0.0) * 1e3
        coverage = round(kernel_ms / compute_ms, 3) \
            if compute_ms > 0 else 0.0
        top = rows[0] if rows else {}
        overhead_pct = round(100.0 * (t_on - t_off) / t_off, 2)
        _KERNELPROF_SUMMARY[0] = {
            "overhead_pct": overhead_pct,
            "coverage": coverage,
            "top": top.get("label"),
            "top_ms": top.get("device_ms"),
            "top_roofline_pct": top.get("roofline_pct"),
        }
        return {
            "metric": "kernelprof_coverage_ratio", "value": coverage,
            "unit": "kernel_ms/compute_ms",
            # >=1.0 means the kernel table explains the compute bucket
            # to within the 20% acceptance band
            "vs_baseline": round(min(1.0, coverage / 0.8), 2)
            if coverage <= 1.2 else round(1.2 / coverage, 2),
            "overhead_pct": overhead_pct,
            "q1_profile_ms": round(t_off * 1e3, 1),
            "q1_kernels_ms": round(t_on * 1e3, 1),
            "kernels": [{k: r.get(k) for k in
                         ("label", "fingerprint", "dispatches",
                          "device_ms", "gflops", "gbps",
                          "roofline_pct", "bound")}
                        for r in rows[:6]],
            "kernel_device_ms": round(kernel_ms, 2),
            "compute_ms": round(compute_ms, 2),
            "catalog_entries": KP.catalog_size(),
        }
    finally:
        KP.disable()  # later benches run raw (wrappers fast-path)


def bench_telemetry_overhead():
    """Engine-wide telemetry acceptance bench (ISSUE 10): TPC-H q1 and
    q5 through the engine with spark.rapids.sql.telemetry.enabled off
    vs on (registry + utilization sampler live).  The disabled path is
    a single module-global read per hook; the enabled path pays only
    the sampler's low-rate probe ticks and pull-based scrapes, and the
    acceptance budget is < 2% wall-clock.  Leaves telemetry RUNNING so
    every later bench gets a per-bench utilization breakdown."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    from spark_rapids_tpu.models.tpch_data import gen_tables
    from spark_rapids_tpu.utils import telemetry as T

    tables = gen_tables(np.random.default_rng(11), 200_000)
    conf_off = C.RapidsConf(dict(BENCH_CONF))
    conf_on = C.RapidsConf({**BENCH_CONF,
                            "spark.rapids.sql.telemetry.enabled": True})
    for q in (1, 5):
        run_query(q, tables, engine="tpu", conf=conf_off)  # warm compile

    def timed(conf, n=3):
        best = {1: float("inf"), 5: float("inf")}
        for _ in range(n):
            for q in (1, 5):
                t0 = time.perf_counter()
                run_query(q, tables, engine="tpu", conf=conf)
                best[q] = min(best[q], time.perf_counter() - t0)
        return best

    T.stop()  # the off measurement must really be off
    t_off = timed(conf_off)
    t_on = timed(conf_on)  # maybe_start fires on the first collect
    util = None
    if T.live() is not None:
        util = T.live().utilization_summary()
    pct = {q: round(100.0 * (t_on[q] - t_off[q]) / t_off[q], 2)
           for q in (1, 5)}
    worst = max(pct.values())
    _TELEMETRY_OVERHEAD_PCT[0] = worst
    return {
        "metric": "telemetry_overhead_pct", "value": worst, "unit": "%",
        # not a speed ratio: >=1.0 means "within the 2% budget"
        "vs_baseline": round(min(2.0, 2.0 / max(worst, 0.01)), 2)
        if worst > 0 else 2.0,
        "q1_off_ms": round(t_off[1] * 1e3, 1),
        "q1_on_ms": round(t_on[1] * 1e3, 1),
        "q1_overhead_pct": pct[1],
        "q5_off_ms": round(t_off[5] * 1e3, 1),
        "q5_on_ms": round(t_on[5] * 1e3, 1),
        "q5_overhead_pct": pct[5],
        "utilization": util,
    }


def bench_residency_overhead():
    """HBM residency-ledger acceptance bench (ISSUE 14): TPC-H q5
    through the engine with profiling on and
    spark.rapids.sql.profile.residency.enabled off vs on.  The ledger
    is dict bookkeeping per tracked alloc/free (no device syncs), so
    the acceptance budget is < 2% on top of the profiled run.  Also
    validates the report: the profiled q5 must show a NONZERO HBM
    high-water mark whose peak-instant composition sums to the mark,
    and a clean leak verdict — the bytes half of the acceptance
    criteria, measured where the wall-clock half is."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    from spark_rapids_tpu.models.tpch_data import gen_tables
    from spark_rapids_tpu.utils import profile as P
    from spark_rapids_tpu.utils import residency as RS

    tables = gen_tables(np.random.default_rng(11), 200_000)
    conf_off = C.RapidsConf({**BENCH_CONF,
        "spark.rapids.sql.profile.enabled": True,
        "spark.rapids.sql.profile.residency.enabled": False})
    conf_on = C.RapidsConf({**BENCH_CONF,
        "spark.rapids.sql.profile.enabled": True,
        "spark.rapids.sql.profile.residency.enabled": True})
    run_query(5, tables, engine="tpu", conf=conf_off)  # warm compile

    # interleaved off/on pairs with ALTERNATING order: back-to-back
    # pairs cancel slow machine-load drift, and flipping which conf
    # goes first each round cancels the position-in-pair bias (the
    # second run of a pair measurably differs on a loaded CPU box —
    # observed at ~2% either way, dwarfing the ledger's actual cost of
    # ~tens of dict ops per query)
    t_off = t_on = float("inf")
    for i in range(5):
        pair = (conf_off, conf_on) if i % 2 == 0 else \
            (conf_on, conf_off)
        for conf in pair:
            t0 = time.perf_counter()
            run_query(5, tables, engine="tpu", conf=conf)
            dt = time.perf_counter() - t0
            if conf is conf_off:
                t_off = min(t_off, dt)
            else:
                t_on = min(t_on, dt)
    # the report assertions need an ON profile to be the last recorded
    run_query(5, tables, engine="tpu", conf=conf_on)
    prof = P.last_profile()
    res = prof.residency or {}
    hwm = int(res.get("hbm_high_water", 0))
    comp = res.get("peak_composition") or {}
    comp_sum = sum(comp.values())
    leaks = int(res.get("leaks", -1))
    top_site = max(comp.items(), key=lambda kv: kv[1])[0] \
        if comp else None
    overhead_pct = round(100.0 * (t_on - t_off) / t_off, 2)
    _RESIDENCY_SUMMARY[0] = {
        "overhead_pct": overhead_pct,
        "hbm_high_water": hwm,
        "leaks": leaks,
        "top_site": top_site,
    }
    try:
        return {
            "metric": "residency_overhead_pct", "value": overhead_pct,
            "unit": "%",
            # not a speed ratio: >=1.0 means "within the 2% budget"
            "vs_baseline": round(min(2.0, 2.0 / max(overhead_pct, 0.01)),
                                 2) if overhead_pct > 0 else 2.0,
            "q5_off_ms": round(t_off * 1e3, 1),
            "q5_on_ms": round(t_on * 1e3, 1),
            # per-lane residency fields bench_diff attributes on
            "hbm_high_water": hwm,
            "peak_composition_sum": comp_sum,
            "peak_reconciles": bool(hwm > 0 and comp_sum == hwm),
            "top_site": top_site,
            "leaks": leaks,
            "allocs": res.get("allocs"),
            "frees": res.get("frees"),
        }
    finally:
        RS.disable()  # later benches register nothing


def bench_pipeline_overlap():
    """Async-pipeline acceptance bench: scan -> filter -> aggregate
    through the REAL exec path over a multi-file parquet dataset, run
    synchronously (pipeline.enabled=false) and pipelined (prefetchDepth
    2).  The pipelined run overlaps host decode + H2D upload with the
    filter/aggregate kernels; the JSON records the speedup, the
    per-partition host-sync count both ways (utils/checks.py debug
    counter), prefetch hit/stall counts, and pipeline wait time, so the
    perf trajectory captures OVERLAP, not just wall clock."""
    import shutil
    import tempfile

    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu import config as C
    from spark_rapids_tpu import io as tio
    from spark_rapids_tpu.exec import pipeline as P
    from spark_rapids_tpu.exprs.aggregates import Count, Sum
    from spark_rapids_tpu.exprs.base import col, lit
    from spark_rapids_tpu.plan.nodes import CpuAggregate, CpuFilter
    from spark_rapids_tpu.plan.overrides import accelerate, collect
    from spark_rapids_tpu.utils import checks as CK

    rows_per_file, n_files = 1 << 20, 8
    n_partitions = 2
    rng = np.random.default_rng(31)
    tmp = tempfile.mkdtemp(prefix="tpu-pipe-bench-")
    try:
        for i in range(n_files):
            df = pd.DataFrame({
                "k": rng.integers(0, 1 << 10,
                                  rows_per_file).astype(np.int64),
                "v": rng.uniform(0, 100, rows_per_file),
                "w": rng.uniform(0, 10, rows_per_file),
            })
            pq.write_table(pa.Table.from_pandas(df),
                           f"{tmp}/part-{i}.parquet")
        total_rows = rows_per_file * n_files
        base = {
            "spark.rapids.sql.variableFloatAgg.enabled": True,
            # a few batches per partition so there is something to
            # run ahead on (1 batch/partition cannot pipeline)
            "spark.sql.files.maxPartitionBytes": 1 << 40,
            "spark.sql.files.minPartitionNum": n_partitions,
            "spark.rapids.tpu.batchMaxRows": 1 << 19,
            "spark.rapids.sql.reader.batchSizeRows": 1 << 19,
        }

        def make_runner(pipe: bool):
            conf = C.RapidsConf(dict(
                base, **{"spark.rapids.sql.pipeline.enabled": pipe,
                         "spark.rapids.sql.pipeline.prefetchDepth": 2}))
            plan = accelerate(CpuAggregate(
                [col("k")],
                [Sum(col("v")).alias("sv"), Sum(col("w")).alias("sw"),
                 Count(col("v")).alias("c")],
                CpuFilter(col("v") >= lit(5.0),
                          tio.read_parquet(tmp))), conf)
            return lambda: collect(plan, conf)

        runs = {pipe: make_runner(pipe) for pipe in (False, True)}
        out = runs[True]()  # cold + correctness vs the sync engine run
        exp = runs[False]()
        got = out.sort_values("k", ignore_index=True)
        exp = exp.sort_values("k", ignore_index=True)
        assert len(got) == len(exp) and \
            (got["c"].astype(int).to_numpy()
             == exp["c"].to_numpy(dtype=np.int64)).all()
        assert np.allclose(got["sv"].astype(float), exp["sv"].astype(float),
                           rtol=1e-6)

        results = {}
        for pipe in (False, True):
            P.reset_pipeline_stats()
            CK.reset_host_syncs()
            best = _best_of(runs[pipe], 3)
            results[pipe] = {
                "best_s": best,
                "syncs_per_partition":
                    CK.host_sync_count() / 3 / n_partitions,
                "stats": P.pipeline_stats(),
            }
        sync_r, pipe_r = results[False], results[True]
        stats = pipe_r["stats"]
        return {
            "metric": "pipeline_overlap_rows_per_sec", "mode": "engine",
            "value": round(total_rows / pipe_r["best_s"], 1),
            "unit": "rows/s",
            "vs_baseline": round(sync_r["best_s"] / pipe_r["best_s"], 2),
            "speedup_vs_sync":
                round(sync_r["best_s"] / pipe_r["best_s"], 3),
            "host_syncs_per_partition":
                round(pipe_r["syncs_per_partition"], 2),
            "host_syncs_per_partition_sync":
                round(sync_r["syncs_per_partition"], 2),
            "prefetch_hits": stats["hits"],
            "prefetch_stalls": stats["stalls"],
            "pipeline_wait_ms": round(stats["wait_ns"] / 1e6, 1),
            "note": "scan->filter->aggregate over 8 parquet files, "
                    "prefetchDepth=2 vs pipeline.enabled=false on this "
                    "machine; vs_baseline here IS the sync-path ratio. "
                    "Host-sync counts come from the utils/checks.py "
                    "debug counter (collect-boundary syncs included).",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


SCALE_LI_BATCH = 1 << 22       # 4M caps: shares kernel signatures with
                               # the other benches (8M-cap bitonic
                               # sorts compile for ~10 minutes each)
SCALE_LI_BATCHES = 25          # 104,857,600 rows


def bench_concurrent_throughput():
    """Multi-query serving bench (ISSUE 6): N concurrent sessions fire
    TPC-H q1/q5 through the admission-controlled scheduler; reports
    aggregate rows/s and p50/p95 per-query latency at 1, 4, and 8
    sessions plus the scheduler's admission counters.  The headline
    value is the 4-session aggregate throughput; vs_baseline is its
    scaling over 1 session (1.0 = no benefit from concurrency, >1 =
    the device idle time one session leaves is being resold)."""
    import threading

    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exec.scheduler import scheduler_stats
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    from spark_rapids_tpu.models.tpch_data import gen_tables

    scale = 20_000
    queries_per_session = 3
    tables = gen_tables(np.random.default_rng(11), scale)
    rows_per_query = sum(len(t) for t in tables.values())
    conf = C.RapidsConf(dict(BENCH_CONF))
    run_query(1, tables, conf=conf)   # warm compile cache
    run_query(5, tables, conf=conf)

    def run_level(n_sessions: int) -> dict:
        latencies: list = []
        errors: list = []
        lat_lock = threading.Lock()
        start = threading.Barrier(n_sessions)

        def session(sid: int):
            try:
                start.wait(timeout=60)
                for k in range(queries_per_session):
                    q = 1 if (sid + k) % 2 == 0 else 5
                    t0 = time.perf_counter()
                    run_query(q, tables, conf=conf)
                    dt = time.perf_counter() - t0
                    with lat_lock:
                        latencies.append(dt)
            except BaseException as e:  # noqa: BLE001
                errors.append(f"{type(e).__name__}: {e}"[:200])

        threads = [threading.Thread(target=session, args=(i,))
                   for i in range(n_sessions)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        lat_ms = sorted(x * 1e3 for x in latencies)

        def pct(p):
            return round(lat_ms[min(len(lat_ms) - 1,
                                    int(p * len(lat_ms)))], 1) \
                if lat_ms else 0.0
        n_q = len(latencies)
        return {"sessions": n_sessions, "queries": n_q,
                "errors": errors,
                "wall_s": round(wall, 3),
                "agg_queries_per_sec": round(n_q / wall, 3),
                "agg_rows_per_sec": round(n_q * rows_per_query / wall),
                "p50_ms": pct(0.50), "p95_ms": pct(0.95)}

    levels = {n: run_level(n) for n in (1, 4, 8)}
    for lv in levels.values():
        assert not lv["errors"], lv["errors"]
    base = levels[1]["agg_rows_per_sec"] or 1
    return {
        "metric": "concurrent_throughput_rows_per_sec",
        "value": levels[4]["agg_rows_per_sec"],
        "unit": "rows/s",
        "vs_baseline": round(levels[4]["agg_rows_per_sec"] / base, 3),
        "scaling_1_to_8": round(levels[8]["agg_rows_per_sec"] / base,
                                3),
        "levels": levels,
        "scheduler": scheduler_stats(),
        "note": "mixed TPC-H q1/q5 from N concurrent sessions through "
                "admission control + the fair-share semaphore; "
                "vs_baseline = 4-session aggregate throughput over "
                "1-session (device idle time resold to other "
                "sessions).",
    }


def bench_scale_join_groupby():
    """Scale evidence (VERDICT r4 #9): a ≥100M-row join+group-by through
    the REAL exec path — multi-batch map side, both inputs exchanged
    through the spillable shuffle catalog, one pass with device->host
    spill FORCED after the map stage and asserted >0 (reducers then
    pull host-tier buffers), plus untampered timing passes.  The
    closest single-chip analog to milestone 4's SF1K pod run
    (reference harness shape: TpcxbbLikeBench.scala:26-40)."""
    import jax.numpy as jnp
    import pandas as pd
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu import types as TT
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exec.joins import HashJoinExec, JoinType
    from spark_rapids_tpu.exprs.aggregates import Count, Sum
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.memory.env import ResourceEnv
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning

    import os
    import sys

    def phase(label, _t=[time.perf_counter()]):
        """Env-gated phase timing (SPARK_RAPIDS_TPU_BENCH_PHASES=1) —
        stderr so the driver-parsed stdout stays clean."""
        now = time.perf_counter()
        if os.environ.get("SPARK_RAPIDS_TPU_BENCH_PHASES"):
            print(f"[scale-phase] {label}: +{now - _t[0]:.1f}s",
                  file=sys.stderr, flush=True)
        _t[0] = now

    n_li = SCALE_LI_BATCH * SCALE_LI_BATCHES
    n_ord, n_cust, n_parts = 1 << 22, 1 << 17, 4
    rng = np.random.default_rng(77)
    li_schema = TT.Schema.of(("l_orderkey", TT.INT64),
                             ("l_revenue", TT.FLOAT64))
    # host-generated once, uploaded batch-wise (the q1 pattern)
    lk = rng.integers(0, n_ord, n_li).astype(np.int64)
    lv = rng.uniform(1.0, 2.0, n_li)
    phase("datagen")
    li_parts = []
    for i in range(SCALE_LI_BATCHES):
        s = slice(i * SCALE_LI_BATCH, (i + 1) * SCALE_LI_BATCH)
        li_parts.append([ColumnarBatch.from_numpy(
            {"l_orderkey": lk[s], "l_revenue": lv[s]}, li_schema)])
    ok = np.arange(n_ord, dtype=np.int64)
    oc = rng.integers(0, n_cust, n_ord).astype(np.int64)
    ord_schema = TT.Schema.of(("o_orderkey", TT.INT64),
                              ("o_custkey", TT.INT64))
    o_parts = [[ColumnarBatch.from_numpy(
        {"o_orderkey": ok, "o_custkey": oc}, ord_schema)]]

    conf = C.RapidsConf({"spark.rapids.shuffle.enabled": True,
                         "spark.rapids.tpu.batchMaxRows": SCALE_LI_BATCH})
    phase("upload (from_numpy x%d)" % (SCALE_LI_BATCHES + 1))

    from spark_rapids_tpu.exec.base import UnaryExecBase

    class SpillTap(UnaryExecBase):
        """Pass-through on the PROBE-side exchange output: fires when
        the join pulls its first reduce batch — the map stage for both
        exchanges has run, their outputs sit in the spillable catalog —
        and forces everything device->host.  Inert (enabled=False)
        during the untampered timing passes.  (Tapping between join
        and agg was too late: the join drains its readers eagerly, so
        the catalog was already empty.)"""
        enabled = False
        spilled = 0

        def output_schema(self):
            return self.child.output_schema()

        def process_partition(self, batches):
            if SpillTap.enabled:
                SpillTap.spilled = max(
                    SpillTap.spilled,
                    ResourceEnv.get().device_store.synchronous_spill(0))
            yield from batches

    lex = ShuffleExchangeExec(
        HashPartitioning([col("l_orderkey")], n_parts),
        LocalBatchSource(li_parts, li_schema))
    oex = ShuffleExchangeExec(
        HashPartitioning([col("o_orderkey")], n_parts),
        LocalBatchSource(o_parts, ord_schema))
    join = HashJoinExec(JoinType.INNER, [col("l_orderkey")],
                        [col("o_orderkey")], SpillTap(lex), oex, None)
    # ONE plan instance for every pass: collect() owns the deferred-
    # check retry protocol (the 131K-group agg escalates its compact
    # width through it), and the learned width persists on the exec
    agg = HashAggregateExec(
        [col("o_custkey")],
        [Sum(col("l_revenue")).alias("rev"),
         Count(col("l_revenue")).alias("n")], join)

    # asserted-spill pass: reducers must read host-tier buffers and
    # stay exact
    phase("plan build")
    # warm pass FIRST (untimed, no spill): compiles + the deopt-retry
    # ladder's learned compact widths happen here.  Without it the
    # asserted-spill pass is the exec's first collect and pays 2-3 full
    # re-executions (each re-spilling the map outputs device->host).
    with C.session(conf):
        agg.collect()
    phase("warm pass (compiles + learned widths)")
    SpillTap.enabled = True
    with C.session(conf):
        got = agg.collect().to_pandas()
    SpillTap.enabled = False
    phase("asserted-spill pass")
    spilled = SpillTap.spilled
    assert spilled > 0, "no device->host spill occurred"
    cust_sums = np.zeros(n_cust)
    np.add.at(cust_sums, oc[lk], lv)
    exp_n = np.bincount(oc[lk], minlength=n_cust)
    got = got.sort_values("o_custkey", ignore_index=True)
    assert len(got) == n_cust
    np.testing.assert_allclose(got["rev"].to_numpy(dtype=float),
                               cust_sums, rtol=1e-9)
    np.testing.assert_array_equal(
        got["n"].to_numpy(dtype=np.int64), exp_n)
    phase("correctness checks")

    def engine_run():
        with C.session(conf):
            agg.collect().to_pandas()
    best = _best_of(engine_run, 2)
    phase("engine timed passes x2")

    ldf = pd.DataFrame({"l_orderkey": lk, "l_revenue": lv})
    odf = pd.DataFrame({"o_orderkey": ok, "o_custkey": oc})

    def pandas_run():
        m = ldf.merge(odf, left_on="l_orderkey", right_on="o_orderkey")
        return m.groupby("o_custkey").agg(rev=("l_revenue", "sum"),
                                         n=("l_revenue", "size"))
    # best-of-2 like the engine side (same fix q1 got): a single pandas
    # pass inflates vs_baseline in the favorable direction whenever the
    # first pass eats a cold page-cache/allocator warmup
    pandas_time = _best_of(pandas_run, 2)
    phase("pandas pass")
    return {
        "metric": "scale_join_groupby_rows_per_sec", "mode": "engine",
        "value": round(n_li / best, 1), "unit": "rows/s",
        "vs_baseline": round(pandas_time / best, 2),
        "effective_gbps": round(n_li * 16 / best / 1e9, 2),
        "rows": n_li,
        "spilled_bytes": int(spilled),
        "note": "104.9M-row join (4.2M-key build) + 131K-group "
                "group-by through exchanges on the spillable shuffle "
                "catalog; the evidence pass forces device->host spill "
                "after the map stage (asserted >0) and reducers read "
                "host-tier buffers exactly; timing passes run "
                "untampered.",
    }


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip: JAX found {dev.platform!r} "
            f"({dev.device_kind}); nothing was run")
    # engine-wide telemetry rides the whole bench run (50ms sampler)
    # so every bench's summary carries a busy-vs-idle-by-cause
    # breakdown — the round report EXPLAINS low HBM utilization
    # instead of just reporting it
    from spark_rapids_tpu import config as _C
    from spark_rapids_tpu.utils import telemetry as T
    T.start(_C.RapidsConf({
        "spark.rapids.sql.telemetry.enabled": True,
        "spark.rapids.sql.telemetry.samplePeriodMs": 50.0}))
    hbm_probe = probe_hbm_bandwidth()
    _HBM_PROBE_GBPS[0] = hbm_probe
    print(json.dumps({"metric": "hbm_probe_gbps",
                      "value": round(hbm_probe, 1), "unit": "GB/s",
                      "note": "device-resident fused read pass — a "
                              "probed chip-side bandwidth"}),
          flush=True)
    q1, pandas_time, batches = bench_q1_stream()
    print(json.dumps(q1), flush=True)
    subs = [q1]
    try:
        fused = bench_q1_fused(pandas_time, batches)
        print(json.dumps(fused), flush=True)
        subs.append(fused)
    except Exception as e:
        err = {"metric": "tpch_q1_fused_rows_per_sec", "value": 0,
               "vs_baseline": 0,
               "error": f"{type(e).__name__}: {e}"[:400]}
        print(json.dumps(err), flush=True)
        subs.append(err)
    try:
        fused_val = next((m.get("value", 0) for m in subs
                          if m["metric"] == "tpch_q1_fused_rows_per_sec"),
                         0)
        eng = bench_q1_engine_fused(pandas_time, batches, fused_val)
        print(json.dumps(eng), flush=True)
        subs.append(eng)
    except Exception as e:
        import traceback
        traceback.print_exc()
        err = {"metric": "tpch_q1_engine_fused_rows_per_sec", "value": 0,
               "vs_baseline": 0,
               "error": f"{type(e).__name__}: {e}"[:400]}
        print(json.dumps(err), flush=True)
        subs.append(err)
    del batches

    # roofline per metric (VERDICT r4 #6): effective input-pass GB/s
    # against the measured HBM probe and nominal v5e HBM
    def add_roofline(m):
        g = m.get("effective_gbps")
        if g is not None:
            m["ceiling_utilization"] = round(g / hbm_probe, 4)
            m["nominal_hbm_utilization"] = _share(g, _nominal_hbm_gbps())

    # driver-facing summary: the driver keeps only a 2000-char tail and
    # parses the FINAL line (BENCH_r03 recorded parsed:null because this
    # line outgrew the window) — so submetrics carry the driver fields +
    # the roofline triple (short keys: gbps / hbm_util = fraction of
    # hbm_probe_gbps / nom_util = fraction of nominal 819 GB/s) and the
    # line length is stepwise-shrunk.
    def compact_at(level: int):
        out = []
        for m in subs:
            e = {k: m[k] for k in ("metric", "value", "vs_baseline")
                 if k in m}
            if level <= 1 and "mode" in m:
                e["mode"] = m["mode"]
            if level <= 2 and "effective_gbps" in m:
                e["gbps"] = m["effective_gbps"]
                e["hbm_util"] = m.get("ceiling_utilization")
                e["nom_util"] = m.get("nominal_hbm_utilization")
            out.append(e)
        return out

    def summary_line():
        # overlap trajectory (ISSUE 2): compile-cache pressure, host
        # sync count, and pipeline wait/hit counters ride the summary
        # so regressions in overlap are visible round-to-round
        from spark_rapids_tpu.exec.base import (kernel_cache_evictions,
                                                kernel_cache_size)
        from spark_rapids_tpu.exec.pipeline import pipeline_stats
        from spark_rapids_tpu.utils import checks as CK
        pstats = pipeline_stats()
        summary = {
            "metric": q1["metric"],
            "value": q1["value"],
            "unit": q1["unit"],
            "vs_baseline": q1["vs_baseline"],
            "hbm_probe_gbps": round(hbm_probe, 1),
            "kernel_cache_size": kernel_cache_size(),
            "kernel_cache_evictions": kernel_cache_evictions(),
            "host_syncs": CK.host_sync_count(),
            "pipeline_wait_ms": round(pstats["wait_ns"] / 1e6, 1),
            "prefetch_hits": pstats["hits"],
            "profile_overhead_pct": _PROFILE_OVERHEAD_PCT[0],
            # per-kernel attribution (ISSUE 13): sampling overhead,
            # kernel-vs-compute coverage, and the hottest kernel
            "kernelprof": _KERNELPROF_SUMMARY[0],
            # per-edge [MB, effective GB/s] from the movement-ledger
            # bench (ISSUE 8): the data-movement trajectory
            "movement_edges": _MOVEMENT_SUMMARY[0],
            # straggler tolerance (ISSUE 9): p95 with speculation+
            # hedging on vs off under the same injected slowdown
            "tail": _TAIL_SUMMARY[0],
            # engine-wide telemetry (ISSUE 10): its wall-clock cost
            # and the run-wide busy-vs-idle-by-cause breakdown
            "telemetry_overhead_pct": _TELEMETRY_OVERHEAD_PCT[0],
            # HBM residency ledger (ISSUE 14): its wall-clock cost and
            # the profiled q5 high-water/leak trajectory
            "residency": _RESIDENCY_SUMMARY[0],
            # out-of-core degradation (ISSUE 16): slowdown + spill
            # traffic when the working set is 10x the HBM budget
            "oocore": _OOCORE_SUMMARY[0],
            "util": (T.live().utilization_summary()
                     if T.live() is not None else None),
        }
        for level in (1, 2, 3):
            summary["submetrics"] = compact_at(level)
            line = json.dumps(summary)
            if len(line) <= 1800:
                break
        if len(line) > 1800:
            summary.pop("submetrics")
            line = json.dumps(summary)
        return line

    for m in subs:
        add_roofline(m)
    # one failing bench must not zero the whole round artifact (record
    # the failure as a metric-shaped error line and keep going), and a
    # DRIVER-side kill mid-bench must not either: re-print the rolling
    # summary after every bench so the final stdout line is always a
    # complete, parseable summary of everything measured so far
    print(summary_line(), flush=True)
    # bench_out_of_core leads the list: the newest lane's evidence must
    # land inside the driver's wall-clock window even when later
    # benches push past it (the r06 timeout lesson)
    for fn in (bench_out_of_core,
               bench_spmd_stage, bench_groupby, bench_groupby_dict_kernel,
               bench_join_sort, bench_exchange_manager,
               bench_pipeline_overlap, bench_profile_overhead,
               bench_kernelprof,
               bench_telemetry_overhead,
               bench_movement_ledger, bench_residency_overhead,
               bench_tail_latency,
               bench_concurrent_throughput,
               bench_udf_q27, bench_scale_join_groupby):
        tl = T.live()
        util_mark = tl.utilization_counts() if tl is not None else None
        try:
            ms = fn()
        except Exception as e:
            import traceback
            traceback.print_exc()
            err = {"metric": fn.__name__, "value": 0, "vs_baseline": 0,
                   "error": f"{type(e).__name__}: {e}"[:400]}
            print(json.dumps(err), flush=True)
            subs.append(err)
            print(summary_line(), flush=True)
            continue
        # per-bench utilization breakdown: samples taken WHILE this
        # bench ran, attributed busy vs idle-by-cause
        util = (T.live().utilization_summary(baseline=util_mark)
                if util_mark is not None and T.live() is not None
                else None)
        for m in (ms if isinstance(ms, list) else [ms]):
            add_roofline(m)
            if util is not None and "util" not in m:
                m["util"] = util
            print(json.dumps(m), flush=True)
            subs.append(m)
        print(summary_line(), flush=True)
    # an errored bench is recorded above as a metric-shaped line so the
    # round stays parseable, and it fails the run
    return 1 if any("error" in m for m in subs) else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
