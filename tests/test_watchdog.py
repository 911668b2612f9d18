"""Query watchdog soak suite (utils/watchdog.py, ISSUE 4).

The liveness contract: every seeded hang site (producer, collective,
shuffle-server, pyudf, compile) must terminate with a descriptive
`TpuQueryTimeout` + diagnostic dump within ~2x its configured deadline
— never a hang, never leaked semaphore permits or producer threads —
and the SAME process must then run a clean query bit-exact vs an
uninjected run.  With the watchdog disabled (or no injection), results
are unchanged.
"""
import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec.base import KernelCache, clear_kernel_cache
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.utils import metrics as M
from spark_rapids_tpu.utils import watchdog as W

#: injection deadlines: small enough for a fast suite, big enough that
#: warm-kernel query progress (ms per batch) never false-fires
DEADLINE = 2.0
POLL = 0.1


@pytest.fixture(autouse=True)
def clean_watchdog():
    W.reset_hang_injection()
    W.begin_query()
    yield
    W.reset_hang_injection()
    W.begin_query()


def _no_leaks(grace: float = 3.0):
    """Assert zero semaphore permits held and zero live producer
    threads (cancelled producers unwind cooperatively — allow a short
    grace for the last poll slice)."""
    sem = TpuSemaphore.get()
    deadline = time.monotonic() + grace
    while time.monotonic() < deadline:
        producers = [t for t in threading.enumerate()
                     if t.name.startswith("tpu-prefetch") and
                     t.is_alive()]
        if sem.holders() == 0 and not producers:
            return
        time.sleep(0.05)
    assert sem.holders() == 0, f"leaked permits: {sem.snapshot()}"
    assert not producers, f"leaked producers: {producers}"


def _wd(site=None, after=0, deadline=DEADLINE, **extra):
    kv = {"spark.rapids.sql.watchdog.taskTimeout": deadline,
          "spark.rapids.sql.watchdog.collectiveTimeout": deadline,
          "spark.rapids.sql.watchdog.compileTimeout": deadline,
          "spark.rapids.sql.watchdog.pollInterval": POLL}
    if site is not None:
        kv["spark.rapids.memory.faultInjection.hangSite"] = site
        kv["spark.rapids.memory.faultInjection.hangAfterBatches"] = after
    kv.update(extra)
    return kv


# ---------------------------------------------------------------------------
# unit: token / heartbeat / scanner
def test_cancel_token_check_raises_with_dump():
    tok = W.CancelToken()
    tok.check()  # not cancelled: no-op
    tok.cancel("stuck somewhere", dump="THE-DUMP")
    with pytest.raises(W.TpuQueryTimeout, match="stuck somewhere") as ei:
        tok.check()
    assert ei.value.dump == "THE-DUMP"
    assert "THE-DUMP" in str(ei.value)
    # one-shot: a second cancel cannot overwrite the first reason
    tok.cancel("other", dump=None)
    assert tok.reason == "stuck somewhere"


def test_watchdog_fires_on_stalled_heartbeat_within_2x_deadline():
    tok = W.begin_query()
    with C.session(C.RapidsConf(_wd(deadline=0.3))):
        hb = W.heartbeat("stalled-unit")
    t0 = time.monotonic()
    try:
        assert tok.wait(2 * 0.3 + 1.0), "watchdog never fired"
        assert time.monotonic() - t0 <= 2 * 0.3 + 0.5
        assert "stalled-unit" in tok.reason
        assert "stalled-unit" in tok.dump
        qs = W.query_stats()
        assert qs["timeouts"] == 1 and qs["cancels"] == 1 \
            and qs["dumps"] == 1
    finally:
        hb.close()


def test_beating_heartbeat_does_not_fire():
    tok = W.begin_query()
    with C.session(C.RapidsConf(_wd(deadline=0.3))):
        hb = W.heartbeat("healthy-unit")
    try:
        t_end = time.monotonic() + 1.0
        while time.monotonic() < t_end:
            hb.beat()
            time.sleep(0.05)
        assert not tok.cancelled
    finally:
        hb.close()


def test_paused_heartbeat_does_not_fire():
    """Backpressure parking (producer on a full queue) must not read
    as a hang."""
    tok = W.begin_query()
    with C.session(C.RapidsConf(_wd(deadline=0.3))):
        hb = W.heartbeat("parked-unit")
    try:
        with hb.pause():
            time.sleep(1.0)
        assert not tok.cancelled
    finally:
        hb.close()


def test_disabled_watchdog_registers_nothing():
    conf = C.RapidsConf({"spark.rapids.sql.watchdog.enabled": False})
    with C.session(conf):
        hb = W.heartbeat("disabled-unit")
    assert hb is W._NULL_HB
    hb.beat()
    with hb.pause():
        pass
    hb.close()
    assert all(h.name != "disabled-unit"
               for h in W.active_heartbeats())


def test_deadline_resolution_conf_beats_global_default():
    # harness default (conftest) loses to an explicit session setting
    conf = C.RapidsConf({C.WATCHDOG_TASK_TIMEOUT.key: 1.25})
    assert W.deadline_for("task", conf) == 1.25
    # unset in the session: the conftest global default applies
    assert W.deadline_for("task", C.RapidsConf()) == 420.0
    assert W.deadline_for("compile", C.RapidsConf()) == 600.0


def test_dump_sections_present():
    dump = W.build_dump()
    for section in ("heartbeats", "semaphore", "prefetch pipeline",
                    "in-flight shuffle fetches", "hang injection",
                    "thread stacks"):
        assert section in dump, f"dump missing section {section!r}"
    assert "MainThread" in dump


def test_cancellable_sleep_aborts_on_cancel():
    tok = W.begin_query()

    def cancel_soon():
        time.sleep(0.2)
        tok.cancel("abort the backoff")

    threading.Thread(target=cancel_soon, daemon=True).start()
    t0 = time.monotonic()
    with pytest.raises(W.TpuQueryTimeout):
        W.cancellable_sleep(30.0)
    assert time.monotonic() - t0 < 2.0


# ---------------------------------------------------------------------------
# satellite: KernelCache single-flight waiter timeout
def test_kernel_single_flight_waiter_timeout_builds_itself():
    """A waiter whose builder peer exceeds the compile deadline must
    fall through and compile in its own thread (benign double compile)
    — never proceed on a possibly-missing cache entry."""
    clear_kernel_cache()
    kc = KernelCache(scope=("wd-single-flight",))
    gate = threading.Event()
    peer_result = []

    def slow_builder():
        gate.wait(20.0)
        return lambda: "slow"

    def claimer():
        with C.session(C.RapidsConf()):
            peer_result.append(kc.get_or_build(("k",), slow_builder))

    t = threading.Thread(target=claimer, daemon=True)
    t.start()
    time.sleep(0.3)  # let the claimer win the build slot
    conf = C.RapidsConf(
        {"spark.rapids.sql.watchdog.compileTimeout": 0.4,
         # scanner quiet: this is the WAIT path, not a detection test
         "spark.rapids.sql.watchdog.taskTimeout": 60.0})
    t0 = time.monotonic()
    with C.session(conf):
        fn = kc.get_or_build(("k",), lambda: (lambda: "fast"))
    assert fn() == "fast"
    assert time.monotonic() - t0 < 5.0
    gate.set()
    t.join(5.0)
    assert peer_result and peer_result[0]() == "slow"
    clear_kernel_cache()


def _slow_first_dispatch(seconds: float):
    """A builder whose kernel spends `seconds` in its FIRST call and
    none after: what a lazy `jax.jit` does on an empty compile cache."""
    calls = []

    def kernel(x):
        calls.append(x)
        if len(calls) == 1:
            time.sleep(seconds)
        return x + 1
    return lambda: kernel


@pytest.mark.parametrize("private", [True, False],
                         ids=["exec-private", "process-global"])
def test_first_dispatch_compile_is_not_the_tasks_staleness(private):
    """A task whose thread sits in the compiler is the compile
    heartbeat's: the first dispatch of a freshly built KernelCache
    kernel runs past the task deadline without the task firing (a cold
    TPC-H q1 compiled 74 + 203 + 12 s inside one exchange-map task and
    was cancelled at 300 s), the task's clock restarts when the compile
    ends, and a warm dispatch pauses nothing."""
    clear_kernel_cache()
    tok = W.begin_query()
    kc = KernelCache() if private else KernelCache(scope=("wd-cold",))
    with C.session(C.RapidsConf(_wd(deadline=0.3, **{
            "spark.rapids.sql.watchdog.compileTimeout": 30.0}))):
        with W.heartbeat("cold-task") as hb:
            fn = kc.get_or_build(("k",), _slow_first_dispatch(1.0))
            assert fn(1) == 2
            assert not tok.cancelled and not hb.fired
            assert time.monotonic() - hb.last_beat < 0.25
            # warm: the same entry, no compile heartbeat, nothing paused
            again = kc.get_or_build(("k",), _slow_first_dispatch(9.0))
            before = {h.name for h in W.active_heartbeats()}
            assert again(2) == 3
            assert {h.name for h in W.active_heartbeats()} == before
            # and the task's own silence still fires it
            assert tok.wait(2 * 0.3 + 1.0), "watchdog never fired"
            assert "cold-task" in tok.reason
    clear_kernel_cache()


def test_compiling_is_watched_and_pauses_this_thread_only():
    """`watchdog.compiling`: a compile-class heartbeat of its own (a
    wedged compiler still times out), this thread's heartbeats paused,
    another thread's left to their own deadline."""
    tok = W.begin_query()
    other = {}
    made = threading.Event()
    done = threading.Event()

    def elsewhere():
        with C.session(C.RapidsConf(_wd(deadline=60.0))):
            with W.heartbeat("other-thread") as hb:
                other["hb"] = hb
                made.set()
                done.wait(10.0)

    t = threading.Thread(target=elsewhere, daemon=True)
    t.start()
    assert made.wait(5.0)
    try:
        with C.session(C.RapidsConf(_wd(deadline=0.3))):
            with W.heartbeat("compiling-task") as hb:
                with W.compiling("compile:probe"):
                    assert hb._paused == 1
                    assert other["hb"]._paused == 0
                    kinds = {h.name: h.kind
                             for h in W.active_heartbeats()}
                    assert kinds["compile:probe"] == "compile"
                    # the compile deadline (0.3 s here) is the one
                    # that fires on a compiler that never returns
                    assert tok.wait(2 * 0.3 + 1.0)
                    assert "compile:probe" in tok.reason
                assert hb._paused == 0 and not hb.fired
    finally:
        done.set()
        t.join(5.0)


# ---------------------------------------------------------------------------
# satellite: leaked producer accounting
def test_leaked_producer_counted_and_stack_logged(monkeypatch, caplog):
    from spark_rapids_tpu.exec import pipeline as P
    monkeypatch.setattr(P, "_JOIN_TIMEOUT_S", 0.2)
    release = threading.Event()

    def wedged():
        yield 1
        release.wait(10.0)  # ignores close(); outlives the join
        yield 2

    before = P.pipeline_stats()["leaked_producers"]
    it = P.PrefetchIterator(wedged(), depth=1)
    assert next(it) == 1
    time.sleep(0.1)  # producer enters the wedged wait
    import logging
    with caplog.at_level(logging.WARNING,
                         logger="spark_rapids_tpu.pipeline"):
        it.close()
    assert P.pipeline_stats()["leaked_producers"] == before + 1
    assert any("survived" in r.message and "wedged" in r.message
               for r in caplog.records)
    dump = W.build_dump()
    assert "leaked_producers" in dump
    release.set()


# ---------------------------------------------------------------------------
# hang-injection soak: TPC-H through the full engine
SCALE = 600


@pytest.fixture(scope="module")
def tables():
    from spark_rapids_tpu.models.tpch_data import gen_tables
    return gen_tables(np.random.default_rng(11), SCALE)


def _run_q(query, tables, extra=None):
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    conf = C.RapidsConf({**BENCH_CONF, **(extra or {})})
    return run_query(query, tables, engine="tpu", conf=conf)


def _assert_bit_exact(expected, got, label):
    pd.testing.assert_frame_equal(expected, got, check_exact=True,
                                  obj=f"{label} (bit-exact)")


@pytest.mark.parametrize("query,site,after", [
    (1, "producer", 1),
    # q5 exercises the join-heavy plan; its cold compiles are the
    # priciest in the suite, so it rides the slow tier + the
    # run_suite.sh watchdog lane instead of tier-1's wall clock
    pytest.param(5, "producer", 2, marks=pytest.mark.slow),
    (1, "compile", 0),
])
def test_tpch_hang_site_times_out_then_runs_clean(tables, query, site,
                                                  after):
    """The acceptance soak: a seeded hang mid-query must (a) raise a
    descriptive TpuQueryTimeout within ~2x the deadline of the moment
    the engine stops progressing, (b) name the stuck site in the dump,
    (c) leak nothing, and (d) leave the process healthy: the same query
    re-runs bit-exact."""
    base = _run_q(query, tables)
    if site == "compile":
        # the injected run must actually compile for the site to fire
        clear_kernel_cache()
    W.reset_hang_injection()
    t0 = time.monotonic()
    with pytest.raises(W.TpuQueryTimeout) as ei:
        _run_q(query, tables, extra=_wd(site=site, after=after))
    elapsed = time.monotonic() - t0
    # wall clock: setup progresses batch-by-batch (warm kernels), so
    # detection lands ~deadline after the hang engages; 2x deadline
    # plus a scheduling margin bounds the whole failed query
    assert elapsed < 2 * DEADLINE + 10.0, f"took {elapsed:.1f}s"
    msg = str(ei.value)
    assert site in msg, f"dump does not name {site}: {msg[:400]}"
    assert "watchdog" in msg
    _no_leaks()
    # same process, clean run: bit-exact vs the pre-injection baseline
    W.reset_hang_injection()
    W.begin_query()
    got = _run_q(query, tables)
    _assert_bit_exact(base, got, f"q{query} after {site} timeout")
    assert TpuSemaphore.get().holders() == 0


def test_watchdog_metrics_charged_to_plan_root(tables):
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF
    from spark_rapids_tpu.plan.overrides import (ExecutionPlanCapture,
                                                 accelerate, collect)
    from spark_rapids_tpu.models.tpch_data import sources
    from spark_rapids_tpu.models.tpch_queries import QUERIES
    conf = C.RapidsConf({**BENCH_CONF,
                         **_wd(site="producer", after=1)})
    W.reset_hang_injection()

    def run(plan):
        return collect(accelerate(plan, conf), conf)

    with pytest.raises(W.TpuQueryTimeout):
        run(QUERIES[1](sources(tables, 2), run))
    plan = ExecutionPlanCapture.last_plan
    m = plan.metrics.as_dict()
    assert m.get(M.NUM_WATCHDOG_TIMEOUTS, 0) >= 1, m
    assert m.get(M.NUM_CANCELS, 0) >= 1, m
    assert m.get(M.WATCHDOG_DUMPS, 0) >= 1, m
    assert m.get(M.SLOWEST_HEARTBEAT, 0) >= DEADLINE * 1000, m


def test_tpch_unaffected_by_enabled_watchdog(tables):
    """watchdog on (default deadlines) vs off: bit-identical results —
    the watchdog only observes."""
    on = _run_q(1, tables)
    off = _run_q(1, tables,
                 extra={"spark.rapids.sql.watchdog.enabled": False})
    _assert_bit_exact(on, off, "q1 watchdog on/off")


# ---------------------------------------------------------------------------
# hang-injection: shuffle-server stall (manager lane, remote peers)
def _reset_shuffle_world():
    from spark_rapids_tpu.memory.env import ResourceEnv
    from spark_rapids_tpu.shuffle.manager import (MapOutputRegistry,
                                                  TpuShuffleManager)
    from spark_rapids_tpu.shuffle.recovery import PeerHealth
    MapOutputRegistry.clear()
    PeerHealth.get().clear()
    for eid in list(TpuShuffleManager._managers):
        TpuShuffleManager._managers[eid].close()
    ResourceEnv.shutdown()


def _mgr_conf(**extra):
    kv = {"spark.rapids.shuffle.enabled": True,
          "spark.rapids.shuffle.localExecutors": 2,
          "spark.rapids.shuffle.bounceBuffers.size": 2048,
          "spark.rapids.shuffle.fetch.maxRetries": 1,
          "spark.rapids.shuffle.fetch.backoff.baseMs": 1.0}
    kv.update(extra)
    return C.RapidsConf(kv)


def _exchange_rows(conf, df):
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    with C.session(conf):
        src = LocalBatchSource.from_pandas(df, num_partitions=4)
        ex = ShuffleExchangeExec(HashPartitioning([col("k")], 3), src)
        return [sorted(zip(b.column("k").to_pylist(b.num_rows),
                           b.column("v").to_pylist(b.num_rows)))
                for it in ex.execute_partitions() for b in it]


def test_shuffle_server_stall_times_out_not_fetchfailed():
    """A wedged shuffle server is a HANG, not a raised error: fetch
    retries cannot fix it and recovery must not spin on it — the
    watchdog cancels and the query ends in TpuQueryTimeout."""
    rng = np.random.default_rng(7)
    df = pd.DataFrame({
        "k": rng.integers(0, 50, 4000).astype(np.int64),
        "v": rng.integers(0, 10**6, 4000).astype(np.int64)})
    _reset_shuffle_world()
    base = _exchange_rows(_mgr_conf(), df)
    _reset_shuffle_world()
    W.reset_hang_injection()
    W.begin_query()
    t0 = time.monotonic()
    with pytest.raises(W.TpuQueryTimeout) as ei:
        _exchange_rows(_mgr_conf(**_wd(site="shuffle-server",
                                       after=1)), df)
    assert time.monotonic() - t0 < 2 * DEADLINE + 10.0
    assert "shuffle" in str(ei.value)
    _no_leaks()
    # process healthy: the same exchange re-runs clean and matches
    _reset_shuffle_world()
    W.reset_hang_injection()
    W.begin_query()
    got = _exchange_rows(_mgr_conf(), df)
    assert got == base
    _reset_shuffle_world()


# ---------------------------------------------------------------------------
# hang-injection: collective (mesh all-to-all) + pyudf worker
def test_collective_hang_times_out():
    import jax
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.parallel.mesh import active_mesh, make_mesh
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    assert len(jax.devices()) >= 8
    mesh = make_mesh(8)
    rng = np.random.default_rng(3)
    schema = T.Schema.of(("k", T.INT64), ("v", T.FLOAT64))
    parts = [[ColumnarBatch.from_numpy({
        "k": rng.integers(0, 50, 200).astype(np.int64),
        "v": rng.normal(size=200)}, schema)] for _ in range(8)]
    conf = C.RapidsConf(_wd(site="collective", after=0, deadline=1.5))
    t0 = time.monotonic()
    with pytest.raises(W.TpuQueryTimeout) as ei:
        with C.session(conf), active_mesh(mesh):
            src = LocalBatchSource(parts, schema=schema)
            ex = ShuffleExchangeExec(HashPartitioning([col("k")], 8),
                                     src)
            sum(b.num_rows for it in ex.execute_partitions()
                for b in it)
    assert time.monotonic() - t0 < 2 * 1.5 + 8.0
    assert "collective" in str(ei.value)
    _no_leaks()


def test_pyudf_worker_hang_times_out_pool_stays_healthy():
    from spark_rapids_tpu.pyudf.daemon import PythonWorkerPool
    df = pd.DataFrame({"x": [1.0, 2.0, 3.0]})
    conf = C.RapidsConf(_wd(site="pyudf", after=0, deadline=1.0))
    t0 = time.monotonic()
    try:
        with pytest.raises(W.TpuQueryTimeout) as ei:
            with C.session(conf):
                PythonWorkerPool.get().run_udf(lambda d: d, df)
        assert time.monotonic() - t0 < 2 * 1.0 + 8.0
        assert "pyudf" in str(ei.value)
        # the pool slot came back: a clean run works in-process
        W.reset_hang_injection()
        W.begin_query()
        with C.session(C.RapidsConf()):
            out = PythonWorkerPool.get().run_udf(lambda d: d * 2, df)
        assert out["x"].tolist() == [2.0, 4.0, 6.0]
    finally:
        PythonWorkerPool.reset()
