"""Async pipelined execution tests (ISSUE 2): PrefetchIterator contract
(bounded depth, error/cancel propagation, semaphore discipline), the
host-sync debug counter, AQE streaming stage materialization, and
bit-exact parity of pipelined vs synchronous execution — including under
OOM fault injection, so split-and-retry still fires on the consuming
side of a prefetch boundary."""
import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec.pipeline import (
    PrefetchIterator, maybe_prefetch, pipeline_stats,
    reset_pipeline_stats)
from spark_rapids_tpu.memory.semaphore import TaskContext, TpuSemaphore
from spark_rapids_tpu.utils import checks as CK
from spark_rapids_tpu.utils import metrics as M


# ---------------------------------------------------------------------------
# PrefetchIterator unit contract
def test_prefetch_passthrough_order():
    it = PrefetchIterator(iter(range(100)), depth=3)
    assert list(it) == list(range(100))


def test_prefetch_empty_source():
    assert list(PrefetchIterator(iter(()), depth=2)) == []


def test_maybe_prefetch_disabled_returns_plain_iter():
    conf = C.RapidsConf({"spark.rapids.sql.pipeline.enabled": False})
    r = maybe_prefetch(iter([1, 2]), conf=conf)
    assert not isinstance(r, PrefetchIterator)
    conf0 = C.RapidsConf({"spark.rapids.sql.pipeline.prefetchDepth": 0})
    assert not isinstance(maybe_prefetch(iter([1]), conf=conf0),
                          PrefetchIterator)


def test_prefetch_error_propagates_after_good_items():
    def src():
        yield 1
        yield 2
        raise RuntimeError("producer exploded")

    it = PrefetchIterator(src(), depth=2)
    got = []
    with pytest.raises(RuntimeError, match="producer exploded"):
        for x in it:
            got.append(x)
    assert got == [1, 2]


def test_prefetch_bounded_depth_backpressure():
    """The producer must never run more than `depth` items ahead: with
    the consumer parked, at most depth items are produced (plus the one
    blocked in the producer's hand)."""
    produced = []
    consumed_gate = threading.Event()

    def src():
        for i in range(50):
            produced.append(i)
            yield i

    it = PrefetchIterator(src(), depth=2)
    assert next(it) == 0
    # give the producer time to run as far ahead as it can
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline and not it.blocked.is_set():
        time.sleep(0.01)
    assert it.blocked.is_set(), "producer should be parked on full queue"
    # item 0 consumed + 2 queued + 1 in the blocked put's hand
    assert len(produced) <= 4
    assert list(it) == list(range(1, 50))
    assert len(produced) == 50
    consumed_gate.set()


def test_prefetch_close_cancels_producer():
    stopped = threading.Event()

    def src():
        try:
            for i in range(10_000):
                yield i
        finally:
            stopped.set()

    it = PrefetchIterator(src(), depth=2)
    assert next(it) == 0
    it.close()
    assert stopped.wait(5.0), "cancelled producer must close its source"
    with pytest.raises(StopIteration):
        next(it)


def test_prefetch_propagates_session_conf_to_producer():
    seen = []
    conf = C.RapidsConf({"spark.rapids.sql.variableFloatAgg.enabled": True})

    def src():
        seen.append(C.get_active_conf()[C.VARIABLE_FLOAT_AGG])
        yield 1

    with C.session(conf):
        it = PrefetchIterator(src(), depth=1)
    assert list(it) == [1]
    assert seen == [True]


def test_prefetch_propagates_retry_flag_to_producer():
    seen = []

    def src():
        seen.append(CK.is_retrying())
        yield 1

    CK.set_retrying(True)
    try:
        it = PrefetchIterator(src(), depth=1)
    finally:
        CK.set_retrying(False)
    assert list(it) == [1]
    assert seen == [True]


# ---------------------------------------------------------------------------
# semaphore discipline
def test_producer_blocked_on_full_queue_never_holds_semaphore():
    """THE pipeline safety property: a producer whose source acquired
    the TPU semaphore must yield it while parked on a full prefetch
    queue, so a concurrent task can use the accelerator."""
    TpuSemaphore.initialize(1)
    sem = TpuSemaphore.get()
    try:
        def src():
            # simulates a scan upload: device work under the semaphore
            sem.acquire_if_necessary()
            for i in range(10):
                yield i

        it = PrefetchIterator(src(), depth=1)
        assert next(it) == 0
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not it.blocked.is_set():
            time.sleep(0.01)
        assert it.blocked.is_set()
        # while the producer is parked, its semaphore hold is yielded:
        # another task can take the single permit immediately
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline \
                and sem.available_permits() < 1:
            time.sleep(0.01)
        assert sem.available_permits() >= 1, (
            "producer blocked on a full prefetch queue is holding "
            "the TPU semaphore")
        with TaskContext(777) as probe:
            sem.acquire_if_necessary(probe)
            assert sem.holds(probe) == 1
            sem.release_if_necessary(probe)
        assert list(it) == list(range(1, 10))
    finally:
        TpuSemaphore.shutdown()


def test_same_task_concurrent_first_acquire_single_permit():
    """Two threads of one task racing acquire_if_necessary must end
    with the task holding exactly one permit (pipeline producer +
    consumer share the creator's TaskContext)."""
    TpuSemaphore.initialize(2)
    sem = TpuSemaphore.get()
    try:
        ctx = TaskContext(42)
        start = threading.Barrier(2)

        def worker():
            TaskContext.set_current(ctx)
            start.wait()
            sem.acquire_if_necessary()

        ts = [threading.Thread(target=worker) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert sem.holds(ctx) == 2          # refcount: one per acquire
        # exactly ONE permit was taken for the task, so one remains
        assert sem.available_permits() == 1
        sem.release_all(ctx)
        # after release_all both permits are free again
        assert sem.available_permits() == 2
    finally:
        TpuSemaphore.shutdown()


# ---------------------------------------------------------------------------
# host-sync debug counter
def test_host_sync_counter_counts_lazy_num_rows():
    import jax.numpy as jnp

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.vector import ColumnVector

    CK.reset_host_syncs()
    col = ColumnVector(T.INT64, jnp.arange(8, dtype=jnp.int64),
                       jnp.ones(8, bool))
    b = ColumnarBatch(T.Schema.of(("x", T.INT64)), [col],
                      jnp.int32(8))  # lazy device count
    base = CK.host_sync_count()
    _ = b.num_rows
    assert CK.host_sync_count() == base + 1
    assert CK.host_sync_sites().get("batch.num_rows", 0) >= 1
    _ = b.num_rows  # memoized: no second sync
    assert CK.host_sync_count() == base + 1


def test_metricset_lazy_resolve_one_sync_per_dtype_wave():
    import jax.numpy as jnp
    ms = M.MetricSet()
    CK.reset_host_syncs()
    for i in range(10):
        ms.add(M.NUM_OUTPUT_ROWS, jnp.int32(i))
    assert CK.host_sync_count() == 0      # adds stay lazy
    assert ms.value(M.NUM_OUTPUT_ROWS) == sum(range(10))
    assert CK.host_sync_sites().get("metrics.resolve") == 1


# ---------------------------------------------------------------------------
# pipelined vs synchronous engine parity
def _tpch_run(query: int, pipe: bool, conf_overrides: dict):
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    from spark_rapids_tpu.models.tpch_data import gen_tables
    tables = gen_tables(np.random.default_rng(23), 2000)
    conf = C.RapidsConf(dict(
        BENCH_CONF, **conf_overrides,
        **{"spark.rapids.sql.pipeline.enabled": pipe,
           "spark.rapids.sql.pipeline.prefetchDepth": 2}))
    return run_query(query, tables, conf=conf)


@pytest.mark.parametrize("query", [1, 5])
def test_tpch_pipelined_bit_exact(query):
    """Pipelining must not change a single bit of q1/q5 output: same
    kernels, same batch grouping, same accumulation order — only WHERE
    the host work runs moves."""
    sync_df = _tpch_run(query, False, {})
    pipe_df = _tpch_run(query, True, {})
    assert list(sync_df.columns) == list(pipe_df.columns)
    assert len(sync_df) == len(pipe_df)
    for name in sync_df.columns:
        a, b = sync_df[name], pipe_df[name]
        if a.dtype == object:
            assert list(a) == list(b), f"col {name}"
        else:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"col {name}")


@pytest.mark.parametrize("query", [1, 5])
def test_tpch_pipelined_bit_exact_under_oom_injection(query):
    """Seeded OOM fault injection under pipelining: producer-side
    reservation failures propagate to the consuming exec, the
    split-and-retry harness fires there, and the result is still
    bit-exact vs the synchronous run under the same injection seed."""
    from spark_rapids_tpu.memory import retry as R
    overrides = {
        "spark.rapids.memory.faultInjection.oomRate": 0.05,
        "spark.rapids.memory.faultInjection.seed": 7,
        "spark.rapids.memory.faultInjection.maxInjections": 64,
    }
    frames = {}
    for pipe in (False, True):
        R.reset_oom_injection()
        frames[pipe] = _tpch_run(query, pipe, overrides)
    R.reset_oom_injection()
    sync_df, pipe_df = frames[False], frames[True]
    assert len(sync_df) == len(pipe_df)
    for name in sync_df.columns:
        a, b = sync_df[name], pipe_df[name]
        if a.dtype == object:
            assert list(a) == list(b), f"col {name}"
        else:
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"col {name}")


def test_groupby_pipelined_matches_pandas():
    from spark_rapids_tpu.exprs.aggregates import Count, Sum
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.plan import (CpuAggregate, CpuSource,
                                       accelerate, collect)
    rng = np.random.default_rng(3)
    df = pd.DataFrame({"k": rng.integers(0, 97, 60_000).astype(np.int64),
                       "v": rng.uniform(0, 10, 60_000)})
    plan = CpuAggregate([col("k")],
                        [Sum(col("v")).alias("sv"),
                         Count(col("v")).alias("c")],
                        CpuSource.from_pandas(df, num_partitions=4))
    conf = C.RapidsConf({
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.pipeline.enabled": True,
        "spark.rapids.sql.pipeline.prefetchDepth": 2})
    reset_pipeline_stats()
    got = collect(accelerate(plan, conf), conf) \
        .sort_values("k", ignore_index=True)
    exp = df.groupby("k").agg(sv=("v", "sum"),
                              c=("v", "size")).reset_index()
    assert np.allclose(got["sv"].astype(float), exp["sv"], rtol=1e-3)
    assert (got["c"].astype(int).to_numpy() == exp["c"].to_numpy()).all()
    assert pipeline_stats()["producers"] > 0, \
        "pipelined run should have spawned prefetch producers"


# ---------------------------------------------------------------------------
# AQE streaming stage materialization
def _aqe_plan(n_rows: int):
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    rng = np.random.default_rng(11)
    df = pd.DataFrame({"k": rng.integers(0, 1000, n_rows).astype(np.int64),
                       "v": rng.uniform(0, 1, n_rows)})
    src = LocalBatchSource.from_pandas(df, num_partitions=3)
    return df, ShuffleExchangeExec(HashPartitioning([col("k")], 4), src)


@pytest.mark.parametrize("pipe", [False, True])
def test_aqe_stage_materialization_row_parity(pipe):
    from spark_rapids_tpu.plan import aqe
    df, ex = _aqe_plan(20_000)
    conf = C.RapidsConf({
        "spark.sql.adaptive.enabled": True,
        "spark.rapids.sql.pipeline.enabled": pipe})
    with C.session(conf):
        stage = aqe.ShuffleQueryStageExec(ex).materialize()
        total = 0
        for it in stage.execute_partitions():
            for b in it:
                total += b.num_rows
        assert total == len(df)
        # stats read AFTER streaming consumption still sees every byte
        assert sum(stage.partition_sizes()) > 0
        # a second read (deopt retry shape) serves the held buckets
        total2 = sum(b.num_rows for it in stage.execute_partitions()
                     for b in it)
        assert total2 == len(df)
        stage.release_buckets()
        assert stage._buckets is None


def test_aqe_streaming_fill_error_propagates():
    from spark_rapids_tpu.plan import aqe

    class BoomExec(Exception):
        pass

    _, ex = _aqe_plan(5_000)
    orig = type(ex).execute_partitions

    def boom(self):
        raise BoomExec("map side died")
    type(ex).execute_partitions = boom
    try:
        conf = C.RapidsConf({"spark.rapids.sql.pipeline.enabled": True})
        with C.session(conf):
            stage = aqe.ShuffleQueryStageExec(ex).materialize()
            with pytest.raises(BoomExec):
                stage.partition_sizes()
    finally:
        type(ex).execute_partitions = orig
