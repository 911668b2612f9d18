"""Query-profile subsystem tests (utils/profile.py): span-tree
parenting across helper threads, Chrome trace validity, structured
event-log coverage for seeded OOM-retry / peer-kill / watchdog runs,
profile-disabled parity (bit-exact, zero tracer objects on the hot
loop), and the bounded profile history.

Wall-clock discipline: ONE profiled TPC-H q5 run (module fixture) backs
all the span-tree/trace/parity assertions; the event-log tests ride
cheap q1 runs.
"""
import functools
import json

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.utils import checks as CK
from spark_rapids_tpu.utils import metrics as M
from spark_rapids_tpu.utils import profile as P

SCALE = 300


@pytest.fixture(autouse=True)
def _clean_profiles():
    P.clear_history()
    yield
    P.clear_history()


@pytest.fixture(scope="module")
def tables():
    from spark_rapids_tpu.models.tpch_data import gen_tables
    return gen_tables(np.random.default_rng(11), SCALE)


def _conf(**extra):
    kv = {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.incompatibleOps.enabled": True,
        "spark.rapids.sql.profile.enabled": True,
    }
    kv.update({k.replace("__", "."): v for k, v in extra.items()})
    return C.RapidsConf(kv)


def _run_q(query, tables, **extra):
    from spark_rapids_tpu.models.tpch_bench import run_query
    return run_query(query, tables, engine="tpu", conf=_conf(**extra))


@pytest.fixture(scope="module")
def q5_profiled(tables):
    """One profiled q5 run shared by the span-tree / Chrome-trace /
    EXPLAIN / parity tests (q5's joins + exchanges give a deep tree
    with producer threads on every pipeline break)."""
    P.clear_history()
    out = _run_q(5, tables)
    prof = P.last_profile()
    assert prof is not None
    return out, prof


# ---------------------------------------------------------------------------
# span tree + thread propagation
def test_span_tree_parenting_across_threads(q5_profiled):
    _, prof = q5_profiled
    by_id = {s.sid: s for s in prof.spans}
    roots = [s for s in prof.spans if s.cat == P.CAT_QUERY]
    assert len(roots) == 1
    root = roots[0]
    # every span's parent chain must terminate at the query root —
    # including spans opened on prefetch producer threads
    for s in prof.spans:
        cur, hops = s, 0
        while cur.parent_id is not None:
            assert cur.parent_id in by_id, (
                f"span {cur.name} has dangling parent {cur.parent_id}")
            cur = by_id[cur.parent_id]
            hops += 1
            assert hops < 1000
        assert cur.sid == root.sid, f"span {s.name} detached from root"
    # thread propagation: spans from the driver AND the pipeline's
    # producer threads (exchange map/reduce prefetch) in one tree
    threads = {s.thread_name for s in prof.spans}
    assert len(threads) >= 3, threads
    assert any(t.startswith("tpu-prefetch") for t in threads), threads
    # a producer's operator spans nest under its producer span
    prod = next(s for s in prof.spans if s.cat == P.CAT_PIPELINE)
    kids = [s for s in prof.spans if s.parent_id == prod.sid]
    assert kids, "producer span has no nested operator spans"


def test_chrome_trace_valid_and_deep(q5_profiled):
    _, prof = q5_profiled
    assert prof.span_depth() >= 4
    blob = json.dumps(prof.chrome_trace())
    trace = json.loads(blob)
    events = trace["traceEvents"]
    assert events
    spans = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert spans and metas
    for e in spans:
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert e["args"]["query_id"] == prof.query_id
    # >= 3 distinct thread lanes, each named by a metadata event
    tids = {e["tid"] for e in spans}
    assert len(tids) >= 3
    assert {e["tid"] for e in metas} >= tids


def test_explain_with_metrics_every_node_annotated(q5_profiled):
    _, prof = q5_profiled
    report = prof.plan_report
    assert report
    for line in report.splitlines():
        # every plan line carries a metric annotation (or an explicit
        # no-metrics marker) — the EXPLAIN-with-metrics contract
        assert line.rstrip().endswith("]"), line
    assert "numOutputRows=" in report
    bd = prof.breakdown
    assert bd["wall_s"] > 0
    assert set(bd) >= {"wall_s", "compute_s", "pipeline_wait_s",
                       "shuffle_s", "compile_s", "retry_block_s"}
    # the human-facing view renders all three sections
    text = prof.explain()
    assert "-- plan with metrics --" in text
    assert "-- wall-clock breakdown --" in text
    assert "-- slowest spans --" in text


def test_attach_and_ref_unit():
    owner = P.begin_query(C.RapidsConf(
        {"spark.rapids.sql.profile.enabled": True}))
    assert owner is not None
    try:
        import threading
        got = {}

        with P.span("outer") as outer:
            ref = P.current_ref()

            def helper():
                with P.attach(ref), P.span("inner") as s:
                    got["parent"] = s.parent_id

            t = threading.Thread(target=helper)
            t.start()
            t.join()
        assert got["parent"] == outer.sid
    finally:
        P.end_query(owner)
    # a stale ref (query over) degrades to a no-op
    with P.attach(ref):
        assert P.span("late") is P._NULL_SPAN


# ---------------------------------------------------------------------------
# event log
def test_event_log_oom_retry_records_and_sinks(tables, tmp_path):
    log_path = tmp_path / "events.jsonl"
    trace_path = tmp_path / "trace-{query_id}.json"
    from spark_rapids_tpu.memory import retry as R
    R.reset_oom_injection()
    out = _run_q(1, tables, **{
        "spark.rapids.memory.faultInjection.oomRate": 0.5,
        "spark.rapids.memory.faultInjection.seed": 7,
        "spark.rapids.memory.faultInjection.maxInjections": 16,
        "spark.rapids.memory.retry.minSplitRows": 64,
        "spark.rapids.sql.profile.eventLog.path": str(log_path),
        "spark.rapids.sql.profile.chromeTrace.path": str(trace_path)})
    R.reset_oom_injection()
    assert len(out) > 0
    prof = P.last_profile()
    kinds = {e["kind"] for e in prof.events}
    assert kinds & {"oom_retry", "oom_split_retry", "oom_fallback"}, kinds
    # the JSONL sink holds the same records, every one carrying the
    # query id
    recs = [json.loads(ln) for ln in
            log_path.read_text().splitlines()]
    assert recs
    assert {r["query_id"] for r in recs} == {prof.query_id}
    assert {r["kind"] for r in recs} == kinds
    # the Chrome trace sink landed too, {query_id} substituted
    real = tmp_path / f"trace-{prof.query_id}.json"
    assert real.exists()
    assert json.loads(real.read_text())["otherData"]["query_id"] \
        == prof.query_id


@pytest.mark.slowish
def test_event_log_peer_kill_records(tables):
    from spark_rapids_tpu.memory.env import ResourceEnv
    from spark_rapids_tpu.shuffle.manager import (
        MapOutputRegistry, TpuShuffleManager)
    from spark_rapids_tpu.shuffle.recovery import PeerHealth

    def reset():
        MapOutputRegistry.clear()
        PeerHealth.get().clear()
        for eid in list(TpuShuffleManager._managers):
            TpuShuffleManager._managers[eid].close()

    reset()
    try:
        out = _run_q(1, tables, **{
            "spark.rapids.shuffle.enabled": True,
            "spark.rapids.shuffle.localExecutors": 2,
            "spark.rapids.shuffle.bounceBuffers.size": 2048,
            "spark.rapids.shuffle.fetch.maxRetries": 1,
            "spark.rapids.shuffle.fetch.backoff.baseMs": 1.0,
            "spark.rapids.shuffle.recovery.blacklist.failureThreshold": 1,
            "spark.rapids.shuffle.transport.faultInjection."
            "peerKillAfterFrames": 1})
        assert len(out) > 0
        prof = P.last_profile()
        kinds = {e["kind"] for e in prof.events}
        assert "fetch_failure" in kinds, kinds
        assert "map_recompute" in kinds, kinds
        assert "stage_retry" in kinds, kinds
        assert {e["query_id"] for e in prof.events} == {prof.query_id}
    finally:
        reset()
        ResourceEnv.shutdown()


def test_watchdog_timeout_event_correlated(tables):
    from spark_rapids_tpu.utils import watchdog as W
    W.reset_hang_injection()
    try:
        with pytest.raises(W.TpuQueryTimeout):
            _run_q(1, tables, **{
                "spark.rapids.memory.faultInjection.hangSite": "producer",
                "spark.rapids.memory.faultInjection.hangAfterBatches": 1,
                "spark.rapids.sql.watchdog.taskTimeout": 2.0,
                "spark.rapids.sql.watchdog.pollInterval": 0.1})
    finally:
        W.reset_hang_injection()
    prof = P.last_profile()
    assert prof is not None  # profile assembled even on error
    timeouts = [e for e in prof.events if e["kind"] == "watchdog_timeout"]
    assert timeouts, {e["kind"] for e in prof.events}
    rec = timeouts[0]
    assert rec["query_id"] == prof.query_id
    assert "producer" in rec["heartbeat"]
    assert rec["dump"] and "watchdog dump" in rec["dump"]
    assert any(e["kind"] == "cancel" for e in prof.events)
    assert any(e["kind"] == "query_error" for e in prof.events)


# ---------------------------------------------------------------------------
# disabled path: parity + zero tracer objects
def test_profile_disabled_bit_exact(q5_profiled, tables):
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
    on, _ = q5_profiled
    P.clear_history()
    off = run_query(5, tables, engine="tpu",
                    conf=C.RapidsConf(dict(BENCH_CONF)))
    assert P.tracer() is None
    assert P.profile_history() == []  # disabled run recorded nothing
    # bit-exact: profiling must observe, never perturb
    pd.testing.assert_frame_equal(
        off.reset_index(drop=True), on.reset_index(drop=True))


def test_disabled_hooks_allocate_nothing():
    # the three hot-loop hooks must be allocation-free when no query is
    # profiled: span() returns one shared null context, wrap_operator
    # returns its input ITERATOR unchanged, event() is a single global
    # read
    assert P.tracer() is None
    assert P.span("a") is P.span("b")
    assert P.span("a") is P._NULL_SPAN

    class _FakeExec:
        def name(self):
            return "Fake"

    it = iter([1, 2, 3])
    assert P.wrap_operator(_FakeExec(), 0, it) is it
    P.event("noop", x=1)  # no tracer: must not raise, must not record
    assert P.profile_history() == []
    assert P.current_ref() is None
    with P.attach(None):
        pass


# ---------------------------------------------------------------------------
# history bound
def test_history_bound_respected(tables):
    for _ in range(3):
        _run_q(1, tables, **{
            "spark.rapids.sql.profile.historySize": 2})
    hist = P.profile_history()
    assert len(hist) == 2
    # oldest first, distinct query ids, newest == last_profile()
    ids = [p.query_id for p in hist]
    assert len(set(ids)) == 2
    assert P.last_profile() is hist[-1]


# ---------------------------------------------------------------------------
# satellite: MetricSet.set_max must queue lazily (no hot-path resolve)
def test_set_max_host_value_no_host_sync():
    import jax.numpy as jnp
    ms = M.MetricSet()
    ms.add("lazy", jnp.asarray(5, jnp.int32))  # queue a device value
    before = CK.host_sync_count()
    for v in (3.0, 9.0, 4.0):
        ms.set_max("peak", v)
    # the regression: set_max used to force a full _resolve (device
    # readback) per call even for host floats
    assert CK.host_sync_count() == before
    assert ms.value("peak") == 9.0
    assert ms.value("lazy") == 5.0


def test_set_max_device_value_resolves_on_read_one_sync():
    import jax.numpy as jnp
    ms = M.MetricSet()
    ms.set_max("peak", jnp.asarray(7, jnp.int32))
    ms.set_max("peak", jnp.asarray(3, jnp.int32))
    before = CK.host_sync_count()
    assert ms.value("peak") == 7.0
    assert CK.host_sync_count() == before + 1  # one stacked wave


def test_set_max_interleaved_with_add_fifo_semantics():
    ms = M.MetricSet()
    ms.add("m", 5.0)
    ms.set_max("m", 3.0)   # max(5,3) = 5
    ms.add("m", 4.0)       # 9
    ms.set_max("m", 20.0)  # 20
    assert ms.value("m") == 20.0


def test_event_kind_registry_rejects_unregistered():
    """Event names are a schema: every kind the engine emits is an
    EV_* constant in utils/profile.py, and emitting an unregistered
    name is an error (the event-log analog of conf registration)."""
    tr = P.QueryTracer(C.RapidsConf({
        "spark.rapids.sql.profile.movement.enabled": False}))
    tr.event(P.EV_CANCEL, reason="fixture")
    assert tr.events()[-1]["kind"] == "cancel"
    with pytest.raises(ValueError, match="unregistered profiler event"):
        tr.event("totally_made_up_event")
    # every constant round-trips through the registry
    assert all(getattr(P, k) in P.EVENT_KINDS
               for k in dir(P) if k.startswith("EV_"))


# ---------------------------------------------------------------------------
# the query's tracer starts at accelerate(): plan-phase spans, the
# parked recording, the upload edge, kernel names
CHUNK_ROWS = 32       # 150-row partitions: four full chunks and a tail


def _q6_plan(tables, conf):
    """An accelerated, not yet collected q6 over two partitions that
    each upload in several chunks."""
    from spark_rapids_tpu.models.tpch_data import sources
    from spark_rapids_tpu.models.tpch_queries import QUERIES
    from spark_rapids_tpu.plan.overrides import accelerate
    src = sources(tables, 2)
    return accelerate(QUERIES[6](src, None), conf), src


def _source_batches(plan):
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    todo, out = [plan], []
    while todo:
        node = todo.pop()
        if isinstance(node, LocalBatchSource):
            out += [b for part in node.partitions for b in part]
        todo += node.children
    return out


@pytest.fixture(scope="module")
def q6_from_accelerate(tables):
    """One profiled accelerate() + two collect()s of its plan, with the
    movement ledger on: (first profile, second profile, source chunks,
    padded device bytes of the source batches)."""
    from spark_rapids_tpu.plan.overrides import collect
    from spark_rapids_tpu.utils import movement as MV
    P.clear_history()
    conf = _conf(spark__rapids__tpu__batchMaxRows=CHUNK_ROWS,
                 spark__rapids__sql__profile__movement__enabled=True)
    plan, src = _q6_plan(tables, conf)
    assert P._ACTIVE == 0 and P.tracer() is None
    batches = _source_batches(plan)
    nbytes = sum(MV.vector_device_bytes(c)
                 for b in batches for c in b.columns)
    first_df = collect(plan, conf)
    first = P.last_profile()
    second_df = collect(plan, conf)
    second = P.last_profile()
    pd.testing.assert_frame_equal(first_df, second_df)
    chunks = sum(-(-len(df) // CHUNK_ROWS)
                 for df in src["lineitem"].partitions)
    assert chunks == len(batches) > 2
    return first, second, chunks, nbytes


def _named(prof, name):
    return [s for s in prof.spans if f"{s.cat}:{s.name}" == name]


def test_one_profile_spans_accelerate_through_readback(q6_from_accelerate):
    first, _, chunks, _ = q6_from_accelerate
    (root,) = [s for s in first.spans if s.cat == P.CAT_QUERY]
    (accel,) = _named(first, "plan:accelerate")
    (upload,) = _named(first, "exec:SourceUpload[s0]")
    converts = _named(first, "exec:upload-convert")
    puts = _named(first, "exec:upload-put")
    readbacks = _named(first, "exec:Readback")
    assert len(converts) == len(puts) == 2          # one a partition
    assert sum(s.args["chunks"] for s in converts) == chunks
    assert sum(s.args["chunks"] for s in puts) == chunks
    assert {s.args["phase"] for s in readbacks} == {"drain", "convert"}
    assert accel.parent_id == root.sid and upload.parent_id == accel.sid
    assert all(s.parent_id == upload.sid for s in converts + puts)
    assert accel.args == {"nodes_in": 3, "tpu_nodes_out": 4,
                          "cpu_islands": 0}
    assert upload.args["batches"] == chunks
    assert upload.args["partitions"] == 2
    assert [s.args["partition"] for s in converts] == \
        [s.args["partition"] for s in puts] == [0, 1]
    assert upload.args["rows"] == sum(s.args["rows"] for s in puts)
    # one query id on everything the profile holds
    assert {e["query_id"] for e in first.events} == {first.query_id}
    assert first.chrome_trace()["otherData"]["query_id"] == first.query_id


def test_plan_phase_children_lie_inside_their_parents_in_time(
        q6_from_accelerate):
    first, _, _, _ = q6_from_accelerate
    by_id = {s.sid: s for s in first.spans}
    checked = 0
    for s in first.spans:
        if s.thread_name != "MainThread" or s.parent_id is None:
            continue
        parent = by_id[s.parent_id]
        if parent.thread_name != "MainThread":
            continue
        assert parent.t0 <= s.t0, (parent.name, s.name)
        assert s.t0 + s.dur_ns <= parent.t0 + parent.dur_ns, \
            (parent.name, s.name)
        checked += 1
    assert checked > 10
    # true start times: the plan phase comes first, the readback last
    (accel,) = _named(first, "plan:accelerate")
    ops = [s for s in first.spans if s.name.startswith("HashAggregate")]
    assert ops and all(accel.t0 + accel.dur_ns <= s.t0 for s in ops)
    assert max(s.t0 for s in _named(first, "exec:Readback")) > \
        max(s.t0 for s in ops)


def test_breakdown_splits_plan_and_upload_from_compute(q6_from_accelerate):
    first, second, _, _ = q6_from_accelerate
    bd = first.breakdown
    (accel,) = _named(first, "plan:accelerate")
    (upload,) = _named(first, "exec:SourceUpload[s0]")
    assert bd["upload_s"] == round(upload.dur_ns / 1e9, 6)
    assert bd["plan_s"] == pytest.approx(
        (accel.dur_ns - upload.dur_ns) / 1e9, abs=2e-6)
    assert bd["between_calls_s"] > 0
    parts = sum(v for k, v in bd.items() if k != "wall_s")
    assert parts == pytest.approx(bd["wall_s"], abs=1e-4)
    assert "plan_s" in first.explain() and "upload_s" in first.explain()
    assert second.breakdown["plan_s"] == second.breakdown["upload_s"] == 0


def test_second_collect_repeats_no_plan_phase_span(q6_from_accelerate):
    first, second, _, _ = q6_from_accelerate
    assert first.query_id != second.query_id
    for name in ("plan:accelerate", "exec:SourceUpload[s0]",
                 "exec:upload-convert", "exec:upload-put"):
        assert _named(first, name) and not _named(second, name), name
    assert len(_named(second, "exec:Readback")) == 2
    assert second.breakdown["between_calls_s"] == 0


def test_upload_edge_counts_the_source_batches(q6_from_accelerate):
    from spark_rapids_tpu.utils import movement as MV
    first, second, _, nbytes = q6_from_accelerate
    edge = first.movement["edges"][MV.EDGE_UPLOAD]
    assert edge["bytes"] == nbytes > 0
    (upload,) = _named(first, "exec:SourceUpload[s0]")
    assert upload.args["device_bytes"] == nbytes
    assert sum(s.args["device_bytes"]
               for s in _named(first, "exec:upload-put")) == nbytes
    assert second.movement["edges"][MV.EDGE_UPLOAD]["bytes"] == 0


def test_upload_spans_count_their_transfers(q6_from_accelerate):
    """`transfers`: host-to-device arrays sent.  A q6 partition's four
    pruned columns are 11 arrays (4 data, 4 validity, 3 float32
    shadows); its full chunks go whole and its ragged tail beside them,
    whatever the chunk count."""
    first, _, chunks, _ = q6_from_accelerate
    (upload,) = _named(first, "exec:SourceUpload[s0]")
    puts = _named(first, "exec:upload-put")
    arrays = 11
    for put in puts:
        assert put.args["chunks"] > 2
        runs = 1 + (put.args["rows"] % CHUNK_ROWS > 0)
        assert put.args["transfers"] == arrays * runs
    assert upload.args["transfers"] == sum(s.args["transfers"]
                                           for s in puts)
    assert upload.args["transfers"] <= arrays * 2 * len(puts) \
        < arrays * chunks


def test_repeated_q6_compiles_nothing_and_the_split_is_one_program(
        tables, monkeypatch):
    """Exact compile requests through accelerate() + collect(): against
    the chunk-by-chunk upload (a byte budget of one chunk) the grouped
    one asks for one program more, the split, and a repeat of the query
    asks for none."""
    import jax
    import jax.monitoring
    from spark_rapids_tpu.columnar import batch as CB
    from spark_rapids_tpu.exec.base import clear_kernel_cache
    from spark_rapids_tpu.models.tpch_data import sources
    from spark_rapids_tpu.models.tpch_queries import QUERIES
    from spark_rapids_tpu.plan.overrides import accelerate, collect
    event = "/jax/compilation_cache/compile_requests_use_cache"
    seen = []

    def listen(name, **_kw):
        if name == event:
            seen.append(name)
    jax.monitoring.register_event_listener(listen)
    conf = _conf(spark__rapids__tpu__batchMaxRows=CHUNK_ROWS,
                 spark__rapids__sql__profile__enabled=False)
    # partitions of one length: one split shape
    even = {k: v.iloc[:len(v) - len(v) % 2] for k, v in tables.items()}
    src = sources(even, 2)

    def requests():
        before = len(seen)
        answer = collect(accelerate(QUERIES[6](src, None), conf), conf)
        return len(seen) - before, answer

    def cold():
        clear_kernel_cache()
        jax.clear_caches()
        return requests()

    try:
        grouped, answer = cold()
        repeat, again = requests()
        monkeypatch.setattr(CB, "UPLOAD_TRANSFER_BYTES", 1)
        per_chunk, reference = cold()
    finally:
        jax.monitoring.unregister_event_listener(listen)
    assert per_chunk > 0, "compile requests are not being counted"
    assert grouped == per_chunk + 1
    assert repeat == 0
    pd.testing.assert_frame_equal(answer, again)
    pd.testing.assert_frame_equal(answer, reference)


def test_accelerate_without_collect_leaves_no_live_tracer(tables):
    from spark_rapids_tpu.utils import movement as MV
    plan, _ = _q6_plan(tables, _conf())
    assert P._ACTIVE == 0 and P._TRACER is None
    assert P.tracer() is None and MV.ledger() is None
    assert P.span("x") is P._NULL_SPAN
    parked = plan._plan_phase
    assert parked.ended and P.attach((parked, parked.root)) is P._NULL_SPAN
    assert [s.name for s in parked.spans()
            if s.cat == P.CAT_PLAN] == ["accelerate"]
    assert P.last_profile() is None          # a profile is collect()'s


def test_accelerate_that_raises_leaves_no_live_tracer(tables):
    from spark_rapids_tpu.models.tpch_data import sources
    from spark_rapids_tpu.plan import nodes as N
    from spark_rapids_tpu.plan.overrides import accelerate
    src = sources(tables, 1)["lineitem"]

    class NoRule(N.CpuNode):
        def output_schema(self):
            return self.child.output_schema()
    with pytest.raises(AssertionError, match="did not run on the TPU"):
        accelerate(NoRule(src),
                   _conf(spark__rapids__sql__test__enabled=True))
    assert P._ACTIVE == 0 and P.tracer() is None
    assert getattr(P._TLS, "plan", None) is None


def test_accelerate_inside_a_profiled_query_records_into_it(tables):
    """No second tracer: a plan built while a query runs (a subquery, an
    AQE re-plan) puts its spans in that query's profile and parks
    nothing."""
    from spark_rapids_tpu.exec import scheduler as S
    conf = _conf()
    scope = S.QueryScope(conf)
    try:
        plan, _ = _q6_plan(tables, conf)
        assert "_plan_phase" not in plan.__dict__
        assert P._ACTIVE == 1
    finally:
        scope.close()
    prof = P.last_profile()
    assert len(_named(prof, "plan:accelerate")) == 1
    assert len(_named(prof, "exec:SourceUpload[s0]")) == 1
    assert P._ACTIVE == 0


def test_profiling_off_accelerate_opens_no_span_and_kernels_are_shared(
        tables, monkeypatch):
    from spark_rapids_tpu.exec import base as EB
    from spark_rapids_tpu.plan.overrides import collect
    made = []
    real_init = P.QueryTracer.__init__
    monkeypatch.setattr(P.QueryTracer, "__init__",
                        lambda self, *a, **k: (made.append(self),
                                               real_init(self, *a, **k))[1])
    opened = []
    monkeypatch.setattr(P._SpanCtx, "__enter__",
                        lambda self: opened.append(self._name))
    conf = _conf(spark__rapids__sql__profile__enabled=False)
    plan, _ = _q6_plan(tables, conf)
    assert "_plan_phase" not in plan.__dict__
    before = dict(EB._GLOBAL_KERNELS)
    collect(plan, conf)
    plan2, _ = _q6_plan(tables, conf)
    collect(plan2, conf)
    assert not made and not opened and P.last_profile() is None
    # the second plan instance built nothing: get_or_build handed out
    # the executables the first one compiled, object for object
    first = {k: v for k, v in EB._GLOBAL_KERNELS.items()
             if k not in before or before[k] is v}
    assert first and all(EB._GLOBAL_KERNELS[k] is v
                         for k, v in first.items())
    scope = plan2.children[0].children[0].kernels
    key = next(k for s, k in EB._GLOBAL_KERNELS if s == scope._scope)
    built = []
    fn = scope.get_or_build(key, lambda: built.append(1))
    assert not built and fn is EB._GLOBAL_KERNELS[(scope._scope, key)]


# -- kernels named on the device ---------------------------------------------
@functools.lru_cache(maxsize=None)
def _kernel_vocabulary():
    """Every literal label a `kp_meta(...)` or `named_jit(...)` call
    site of the package passes, with the f-string phases spelled out."""
    import os
    import re
    import spark_rapids_tpu
    root = os.path.dirname(spark_rapids_tpu.__file__)
    site = re.compile(r'(kp_meta|named_jit)\(\s*f?"([^"]+)"')
    found = {"kp_meta": set(), "named_jit": set()}
    for folder, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as f:
                    for kind, label in site.findall(f.read()):
                        found[kind].add(label)
    return found


def _spell(labels):
    return sorted({lab.replace("{phase}", ph) for lab in labels
                   for ph in ("update", "merge")})


def test_kernel_names_and_kernelprof_labels_are_one_vocabulary():
    found = _kernel_vocabulary()
    # the two exchange kernels and the two mesh programs belong to no
    # exec, so nothing hands kernelprof a label for them
    own = {"exchange-split", "exchange-cut", "mesh-exchange", "mesh-count"}
    assert found["named_jit"] - own == found["kp_meta"]
    # plus `sort` / `sort-head` and the fused stage's, passed by name
    assert own <= found["named_jit"] and len(found["named_jit"]) == 23


@pytest.mark.parametrize("label", _spell(
    _kernel_vocabulary()["named_jit"]) + [
        "sort", "sort-head", "fused-3-project-filter-project"])
def test_lowered_module_is_named_for_its_label(label):
    import re
    import jax.numpy as jnp
    from spark_rapids_tpu.exec import base as EB
    kern = EB.named_jit(label, lambda x: x + 1)
    text = kern.lower(jnp.ones(8)).as_text()
    name = re.search(r"module @(\w+)", text).group(1)
    assert name == "jit_" + label.replace("-", "_")
    assert not re.search(r"\d{2,}", name)       # no shape, no hash


def test_built_kernels_carry_their_label_as_their_name(tables):
    """Under kernelprof every cached executable knows the label its
    call site passed: it is the executable's name, so the device trace
    and the host's kernel table speak of the same kernel."""
    from spark_rapids_tpu.exec import base as EB
    from spark_rapids_tpu.utils import kernelprof as KP
    try:
        for q in (3, 6, 1):
            _run_q(q, tables,
                   spark__rapids__sql__profile__kernels__enabled=True)
        # an entry with an owner was labelled by its call site
        seen = {fn._kp_entry.label: fn._kp_fn.__name__
                for fn in list(EB._GLOBAL_KERNELS.values())
                if isinstance(fn, KP.WatchedKernel) and fn._kp_entry.owners}
        assert {"agg-update", "agg-merge", "sort"} <= set(seen), seen
        for label, name in seen.items():
            assert name == EB.kernel_name(label), (label, name)
    finally:
        KP.reset()


def test_a_fused_stage_names_its_members_and_no_shape():
    from spark_rapids_tpu.exec import base as EB
    from spark_rapids_tpu.plan.fusion import ComposedStage

    class ProjectExec:
        pass

    class FilterExec:
        pass
    stage = ComposedStage([], [], None, None,
                          [ProjectExec(), FilterExec(), ProjectExec()])
    assert stage.kernel_label == "fused-3-project-filter-project"
    stage = ComposedStage([], [], None, None, [ProjectExec()] * 40)
    name = EB.kernel_name(stage.kernel_label)
    assert name.startswith("fused_40_project_") and \
        len(name) == EB.KERNEL_NAME_MAX


# -- one span per exec, partition and phase: join, exchange, group-by --------
Q3_SCALE = 20_000
PHASES = (P.SPAN_JOIN_BUILD, P.SPAN_JOIN_PROBE, P.SPAN_EXCHANGE_WRITE,
          P.SPAN_EXCHANGE_READ, P.SPAN_GROUPBY_UPDATE, P.SPAN_GROUPBY_MERGE)


def _q3_tables(seed):
    from benchmark.gen import tpch
    return tpch.generate(seed, Q3_SCALE,
                         ["customer", "orders", "lineitem"])


def _q3(tables, conf):
    from spark_rapids_tpu.models.tpch_data import sources
    from spark_rapids_tpu.models.tpch_queries import QUERIES
    from spark_rapids_tpu.plan.overrides import accelerate, collect
    plan = accelerate(QUERIES[3](sources(tables, 2), None), conf)
    return plan, collect(plan, conf)


def _nodes(plan, suffix):
    todo, out = [plan], []
    while todo:
        node = todo.pop()
        out += [node] if type(node).__name__.endswith(suffix) else []
        todo += node.children
    return out


@pytest.fixture(scope="module")
def q3_profiled():
    """A small q3 over two partitions under the default lanes, run once
    unprofiled (so no span holds a compile) and once profiled."""
    P.clear_history()
    tables = _q3_tables(11)
    _q3(tables, _conf(spark__rapids__sql__profile__enabled=False))
    assert P.last_profile() is None
    plan, answer = _q3(tables, _conf())
    assert len(answer) == 10
    return plan, P.last_profile()


def test_phase_spans_exist_once_per_exec_partition_and_phase(q3_profiled):
    plan, prof = q3_profiled
    joins = _nodes(plan, "JoinExec")
    exchanges = _nodes(plan, "ShuffleExchangeExec")
    aggs = _nodes(plan, "HashAggregateExec")
    assert len(joins) == 2 and len(exchanges) == 4 and len(aggs) == 1
    count = {name: len(_named(prof, f"exec:{name}")) for name in PHASES}
    # a join over co-partitioned exchanges builds and probes once a
    # partition, and hands the aggregate its two partitions
    assert [j.output_partition_count() for j in joins] == [2, 2]
    assert count == {
        P.SPAN_JOIN_BUILD: 2 * len(joins), P.SPAN_JOIN_PROBE: 2 * len(joins),
        P.SPAN_EXCHANGE_WRITE: len(exchanges),
        # a reader per reduce partition of every exchange
        P.SPAN_EXCHANGE_READ: 2 * len(exchanges),
        P.SPAN_GROUPBY_UPDATE: 2, P.SPAN_GROUPBY_MERGE: 2}
    assert prof.dropped_spans == 0


def test_phase_spans_lie_inside_operator_spans_in_time(q3_profiled):
    _, prof = q3_profiled
    import re
    pulls = [s for s in prof.spans
             if s.cat == P.CAT_EXEC and re.search(r"\[p\d+\]$", s.name)]
    assert pulls
    # a phase runs inside some operator's pull, or inside the map side
    # of the exchange above it: a join over exchanges makes its
    # children's iterators (and so runs their map sides, and the first
    # join under them) before its first partition is pulled
    holds = pulls + _named(prof, "exec:exchange-write")
    for name in PHASES:
        for s in _named(prof, f"exec:{name}"):
            assert s.dur_ns > 0
            assert any(o is not s and o.t0 <= s.t0 and
                       s.t0 + s.dur_ns <= o.t0 + o.dur_ns for o in holds) \
                or name == P.SPAN_EXCHANGE_WRITE, name
    # a join builds before it probes, a group-by updates before it merges
    for first, then in ((P.SPAN_JOIN_BUILD, P.SPAN_JOIN_PROBE),
                        (P.SPAN_GROUPBY_UPDATE, P.SPAN_GROUPBY_MERGE)):
        a = sorted(_named(prof, f"exec:{first}"), key=lambda s: s.t0)
        b = sorted(_named(prof, f"exec:{then}"), key=lambda s: s.t0)
        assert a[0].t0 + a[0].dur_ns <= b[-1].t0 + b[-1].dur_ns


def test_phase_spans_carry_their_args(q3_profiled):
    plan, prof = q3_profiled
    probes = _named(prof, "exec:join-probe")
    for s in probes:
        assert set(s.args) == {"lane", "probe_batches", "rows_in",
                               "rows_out", "capacity_rows", "expand_syncs",
                               "partition"}
        assert s.args["lane"] == "sort"
        # partition p probes with probe partition p alone: one merged
        # batch from its exchange reader
        assert s.args["probe_batches"] == s.args["expand_syncs"] == 1
        assert s.args["capacity_rows"] >= s.args["rows_in"] > 0
    assert sorted(s.args["partition"] for s in probes) == [0, 0, 1, 1]
    updates = _named(prof, "exec:groupby-update")
    merges = _named(prof, "exec:groupby-merge")
    assert len(updates) == len(merges) == 2
    # the first join's rows are the second's probe rows, and the second's
    # the group-by's, summed over the partitions
    second = sorted(probes, key=lambda s: s.t0 + s.dur_ns)[-2:]
    first = [s for s in probes if s not in second]
    assert sum(u.args["rows_in"] for u in updates) == \
        sum(s.args["rows_out"] for s in second)
    assert sum(s.args["rows_in"] for s in second) == \
        sum(s.args["rows_out"] for s in first)
    for update, merge in zip(updates, merges):
        assert update.args["phase"] == "update"
        # q3 sums a FLOAT64 expression: float64 in the grouped kernel
        # (its few-groups body or its sort body, as the batch has
        # groups), whatever the (default-on) lane switches say
        assert update.args["lane"] == "few-or-sort"
        # one partial a partition: there is nothing to merge it with
        assert merge.args["partials"] == update.args["batches"] == 1
        assert merge.args["lane"] is None and merge.args["rounds"] == 0
    (agg,) = _nodes(plan, "HashAggregateExec")
    # the group count is on the host only where a sync already brought it
    groups = [m.args["groups"] for m in merges]
    assert None in groups or sum(groups) == \
        agg.metrics.value(M.NUM_OUTPUT_ROWS)
    builds = _named(prof, "exec:join-build")
    assert sorted(s.args["partition"] for s in builds) == [0, 0, 1, 1]
    for s in builds:
        assert set(s.args) == {"rows", "capacity_rows", "slices",
                               "count_reads", "partition"}
        assert s.args["capacity_rows"] >= s.args["rows"] > 0
        # partition p builds from build partition p alone: one tight
        # batch from its merged exchange reader, count known: the build
        # asks the device nothing at this scale
        assert s.args["slices"] == 1 and s.args["count_reads"] == 0
    writes = _named(prof, "exec:exchange-write")
    reads = _named(prof, "exec:exchange-read")
    assert all(s.args["partitions"] == 2 and s.args["bytes"] > 0
               and s.args["capacity_rows"] > 0 for s in writes)
    assert sum(s.args["slices"] for s in reads) == \
        sum(s.args["slices"] for s in writes)
    assert sum(s.args["rows"] for s in reads) == sum(
        x.metrics.value(M.NUM_OUTPUT_ROWS)
        for x in _nodes(plan, "ShuffleExchangeExec"))


def test_profiling_off_opens_no_phase_span(monkeypatch):
    opened = []
    real = P.PhaseSpan.__init__
    monkeypatch.setattr(P.PhaseSpan, "__init__",
                        lambda self, *a, **k: (opened.append(a),
                                               real(self, *a, **k))[1])
    monkeypatch.setattr(P._SpanCtx, "__enter__",
                        lambda self: opened.append(self._name))
    _, answer = _q3(_q3_tables(11),
                    _conf(spark__rapids__sql__profile__enabled=False))
    assert len(answer) == 10 and not opened
    assert P.last_profile() is None and P._ACTIVE == 0


def test_what_a_new_seed_asks_the_compiler_for():
    """Exact compile requests of a small q3 through accelerate() +
    collect(): a repeat asks for nothing; another seed asks only for
    the shapes whose data-dependent capacity bucket (a build side, a
    join's `out_cap`, an exchange's tight cut, the group count) fell in
    another power of two.  At 20,000 lineitem rows the counts sit near
    their buckets' edges and move with the seed; at the benchmark's
    1,500,000 three seeds asked for nothing new (PERF.md, PR 29).  The
    batch helpers run as one named program each (`jit_join_concat`,
    `jit_exchange_slice`, ...), not as chains of eager operations that
    each compile: a cold q3 asked for 131 programs before, 48 then, 44
    once the join ran partition by partition (no build concat of two
    slices, no merge of two partials), and 43 since the collect reads a
    lone row count directly, not through an eager cast, reshape and
    stack."""
    import jax
    import jax.monitoring
    from spark_rapids_tpu.exec.base import clear_kernel_cache
    event = "/jax/compilation_cache/compile_requests_use_cache"
    seen = []

    def listen(name, **_kw):
        if name == event:
            seen.append(name)
    conf = _conf(spark__rapids__sql__profile__enabled=False)

    def requests(seed):
        tables = _q3_tables(seed)
        before = len(seen)
        _, answer = _q3(tables, conf)
        assert len(answer) == 10
        return len(seen) - before

    clear_kernel_cache()
    jax.clear_caches()
    jax.monitoring.register_event_listener(listen)
    try:
        first = requests(11)
        counts = [requests(11), requests(12), requests(13), requests(13)]
    finally:
        jax.monitoring.unregister_event_listener(listen)
    # (two of a cold process's programs outlive `jax.clear_caches()`)
    assert first in (43, 45), first
    # seed 12 lands in seed 11's buckets; seed 13's build sides and
    # group count do not (512 / 256 where 11 had 1024 / 128)
    assert counts == [0, 0, 16, 0]
