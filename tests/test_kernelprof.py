"""Per-kernel performance attribution tests (utils/kernelprof.py):
disabled-path parity (no wrappers, no allocation, bit-exact), the
sampled timing lane (rate honored, compile excluded, per-query
isolation under a concurrent scheduler storm), XLA cost capture and
the roofline join, the '-- kernels --' profile section with inline
EXPLAIN annotations, the slow-query log's top_kernel field, and the
single conf-overridable roofline source shared with the movement
ledger.

Wall-clock discipline (test_profile.py's): ONE warmed, fully-sampled
TPC-H q1 run (module fixture) backs the report/section/catalog
assertions; unit tests drive KernelCache/WatchedKernel directly.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from pandas.testing import assert_frame_equal

from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec.base import KernelCache
from spark_rapids_tpu.utils import kernelprof as KP
from spark_rapids_tpu.utils import movement as MV
from spark_rapids_tpu.utils import profile as P
from spark_rapids_tpu.utils import roofline as RL

SCALE = 300


@pytest.fixture(autouse=True)
def _clean():
    P.clear_history()
    yield
    P.clear_history()
    KP.reset()


@pytest.fixture(scope="module")
def tables():
    from spark_rapids_tpu.models.tpch_data import gen_tables
    return gen_tables(np.random.default_rng(11), SCALE)


def _conf(**extra):
    kv = {
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.incompatibleOps.enabled": True,
    }
    kv.update({k.replace("__", "."): v for k, v in extra.items()})
    return C.RapidsConf(kv)


def _kconf(**extra):
    return _conf(**{
        "spark.rapids.sql.profile.enabled": True,
        "spark.rapids.sql.profile.kernels.enabled": True,
        "spark.rapids.sql.profile.kernels.sampleRate": 1,
        **{k.replace("__", "."): v for k, v in extra.items()}})


def _run_q(query, tables, conf):
    from spark_rapids_tpu.models.tpch_bench import run_query
    return run_query(query, tables, engine="tpu", conf=conf)


@pytest.fixture(scope="module")
def q1_profiled(tables):
    """(reference df, q1 df, QueryProfile, catalog snapshot) from a
    WARMED q1 with every dispatch sampled — shared by the
    report/section/catalog tests.  Pipelining off so sampled kernel
    time and the compute bucket are both single-thread quantities.
    The catalog is snapshotted here because the per-test cleanup
    resets it."""
    KP.reset()
    P.clear_history()
    ref = _run_q(1, tables, _conf())
    conf = _kconf(**{"spark.rapids.sql.pipeline.enabled": False})
    _run_q(1, tables, conf)   # warm: first dispatches charge compile
    got = _run_q(1, tables, conf)
    prof = P.last_profile()
    cat = KP.catalog()
    yield ref, got, prof, cat
    KP.reset()


# ---------------------------------------------------------------------------
# disabled path: no wrappers, no allocation, bit-exact
def test_disabled_path_no_wrappers():
    assert not KP.enabled()
    kc = KernelCache()  # private cache

    def build():
        return jax.jit(lambda x: x + 1)

    fn = kc.get_or_build(("unit-disabled",), build)
    assert not isinstance(fn, KP.WatchedKernel)
    assert int(fn(jnp.int32(1))) == 2
    assert KP.catalog_size() == 0


def test_disabled_hooks_allocate_nothing():
    assert not KP.enabled()

    class _E:
        exec_id = 999991

        def describe(self):
            return "E"

    from spark_rapids_tpu.exec.base import TpuExec
    assert TpuExec.kp_meta(_E(), "label") is None
    assert KP.maybe_enable(_conf()) is False
    assert not KP.enabled()


def test_disabled_query_records_nothing(tables):
    out = _run_q(1, tables, _conf(**{
        "spark.rapids.sql.profile.enabled": True}))
    assert len(out) > 0
    prof = P.last_profile()
    assert prof is not None
    assert prof.kernels is None
    assert prof.kernel_samples == []
    assert "-- kernels --" not in prof.explain()


# ---------------------------------------------------------------------------
# enabled: parity + the report
def test_enabled_bit_exact_and_report(q1_profiled):
    ref, got, prof, _ = q1_profiled
    assert_frame_equal(got.reset_index(drop=True),
                       ref.reset_index(drop=True))
    rows = prof.kernels
    assert rows, "no kernel attribution rows"
    assert all(len(r["fingerprint"]) == 12 for r in rows)
    assert sum(r["dispatches"] for r in rows) > 0
    assert sum(r["device_ms"] for r in rows) > 0
    # rows arrive hottest-first
    ms = [r["device_ms"] for r in rows]
    assert ms == sorted(ms, reverse=True)
    ex = prof.explain()
    assert "-- kernels --" in ex
    assert rows[0]["fingerprint"] in ex


def test_cost_capture_and_roofline_join(q1_profiled):
    _, _, prof, cat = q1_profiled
    roofed = [r for r in prof.kernels if "roofline_pct" in r]
    assert roofed, "no kernel carried a cost/roofline join"
    for r in roofed:
        assert r["flops_per_dispatch"] >= 0
        assert r["bytes_per_dispatch"] > 0
        assert r["gbps"] > 0
        # the CPU test device has no entry in roofline.DEVICE_PEAKS:
        # no share is graded against another chip's peaks
        assert r["roofline_pct"] is None and r["bound"] is None
    assert RL.hbm_gbps(C.RapidsConf()) is None
    assert RL.DEVICE_PEAKS["TPU v5 lite"] == {
        "hbm_gbps": 819.0, "peak_gflops": 197000.0}
    assert any(c["cost"] for c in cat)
    fams = {c["family"] for c in cat}
    assert any("/" in f for f in fams), fams


def test_coverage_vs_compute_bucket(tables):
    """The acceptance shape: summed per-kernel device time explains
    the single-thread compute bucket.  Needs a kernel-DOMINATED scale
    — at the module fixture's tiny SCALE the query is fixed Python
    orchestration and legitimately low-coverage — so this test runs
    its own q1 at 20k rows (generous CI band)."""
    from spark_rapids_tpu.models.tpch_data import gen_tables
    big = gen_tables(np.random.default_rng(11), 20_000)
    conf = _kconf(**{"spark.rapids.sql.pipeline.enabled": False})
    _run_q(1, big, conf)   # warm
    _run_q(1, big, conf)
    prof = P.last_profile()
    kernel_ms = sum(r["device_ms"] for r in prof.kernels)
    compute_ms = prof.breakdown["compute_s"] * 1e3
    assert compute_ms > 0
    cov = kernel_ms / compute_ms
    assert 0.35 <= cov <= 1.5, \
        f"kernel/compute coverage wildly off: {cov}"


def test_explain_inline_annotations(q1_profiled):
    _, _, prof, _ = q1_profiled
    lines = prof.plan_report.splitlines()
    annotated = [l for l in lines if "[kernel " in l]
    assert annotated, "no inline kernel annotations in EXPLAIN"
    # fused member lines carry the owning stage kernel's roofline
    member_annotated = [l for l in annotated if l.lstrip().
                        startswith("* ")]
    assert member_annotated, "fused member lines not annotated"
    # ... when the device has nominal peaks; the CPU test device has
    # none, so no line claims a share
    assert not any("roofline" in l for l in annotated)
    # the report contract other lanes assert: every line ends with ]
    assert all(l.rstrip().endswith("]") for l in lines)


def test_perfetto_kernel_tracks(q1_profiled):
    _, _, prof, _ = q1_profiled
    ev = [e for e in prof.chrome_trace()["traceEvents"]
          if e.get("cat") == "kernel"]
    assert ev, "no kernel events in the Chrome trace"
    for e in ev:
        assert e["ph"] == "X" and e["dur"] > 0
        assert e["args"]["fingerprint"]
        assert e["args"]["query_id"] == prof.query_id


# ---------------------------------------------------------------------------
# sampling mechanics (unit)
def test_sample_rate_honored_and_compile_excluded():
    KP.enable(_conf(**{
        "spark.rapids.sql.profile.kernels.enabled": True,
        "spark.rapids.sql.profile.kernels.sampleRate": 4,
        "spark.rapids.sql.profile.kernels.costAnalysis": False}))
    kc = KernelCache(scope=("kp-unit-rate",))
    fn = kc.get_or_build(("k",), lambda: jax.jit(lambda x: x * 2))
    assert isinstance(fn, KP.WatchedKernel)
    for i in range(40):
        assert int(fn(jnp.int32(i))) == 2 * i
    e = fn._kp_entry
    assert e.dispatches == 40
    # dispatch 1 is the compile bracket (charged to compile_ns, never
    # the histogram); then every 4th dispatch samples: 4, 8, ..., 40
    assert e.sampled == 10, e.sampled
    assert e.compile_ns > 0
    assert e.device_ns > 0
    assert sum(e.snapshot()["hist"]) == e.sampled


def test_wrapper_transparency_and_upgrade_on_hit():
    kc = KernelCache(scope=("kp-unit-upgrade",))

    def build():
        k = jax.jit(lambda x: x - 1)
        k._site_attr = "ride-along"
        return k

    raw = kc.get_or_build(("k",), build)
    assert not isinstance(raw, KP.WatchedKernel)
    KP.enable(_conf(**{
        "spark.rapids.sql.profile.kernels.enabled": True,
        "spark.rapids.sql.profile.kernels.costAnalysis": False}))
    fn = kc.get_or_build(("k",), build)
    assert isinstance(fn, KP.WatchedKernel)
    # reads fall through to the wrapped jit; writes shadow on the proxy
    assert fn._site_attr == "ride-along"
    fn._mark = True
    assert fn._mark is True
    assert int(fn(jnp.int32(3))) == 2
    assert fn._kp_entry.dispatches == 1
    # disabling degrades to passthrough: no further dispatch counting
    KP.disable()
    assert int(fn(jnp.int32(4))) == 3
    assert fn._kp_entry.dispatches == 1


def test_meta_annotation_reaches_catalog():
    KP.enable(_conf(**{
        "spark.rapids.sql.profile.kernels.enabled": True,
        "spark.rapids.sql.profile.kernels.costAnalysis": False}))
    kc = KernelCache(scope=("kp-unit-meta",))
    fn = kc.get_or_build(
        ("k",), lambda: jax.jit(lambda x: x),
        meta={"label": "unit-kernel", "owner_id": 424242,
              "owner": "UnitExec(x)", "members": ["A", "B"]})
    e = fn._kp_entry
    assert e.label == "unit-kernel"
    assert e.members == ["A", "B"]
    assert "UnitExec(x)" in e.owners.values()


# ---------------------------------------------------------------------------
# per-query isolation under the scheduler storm
def test_storm_keeps_per_query_isolation(tables):
    """8 concurrent sessions (mixed q1/q5), every dispatch sampled:
    results bit-exact vs serial, one profile per query, and each
    query's kernel rows describe ITS dispatches (no cross-query
    bleed)."""
    ref = {q: _run_q(q, tables, _conf()) for q in (1, 5)}
    P.clear_history()
    conf = _kconf()
    results, errors = {}, []

    def worker(i, q):
        try:
            results[i] = (q, _run_q(q, tables, conf))
        except BaseException as e:  # noqa: BLE001
            errors.append((i, q, repr(e)))

    mix = [1, 5, 1, 5, 1, 5, 1, 5]
    ts = [threading.Thread(target=worker, args=(i, q))
          for i, q in enumerate(mix)]
    [t.start() for t in ts]
    [t.join(300) for t in ts]
    assert not errors, errors
    for i, (q, df) in results.items():
        assert_frame_equal(df.reset_index(drop=True),
                           ref[q].reset_index(drop=True))
    profs = P.profile_history()
    assert len(profs) == len(mix)
    assert len({p.query_id for p in profs}) == len(mix)
    for p in profs:
        assert p.kernels, f"{p.query_id} recorded no kernel rows"
        assert sum(r["dispatches"] for r in p.kernels) > 0
        # every sample this query recorded belongs to its own window
        for t0, dur, fp, label, tid in p.kernel_samples:
            assert dur > 0


# ---------------------------------------------------------------------------
# slow-query log + telemetry surface
def test_slow_query_log_top_kernel_and_prometheus(tables):
    from spark_rapids_tpu.utils import telemetry as T
    T.stop()
    t = T.start(_conf(**{
        "spark.rapids.sql.telemetry.enabled": True,
        "spark.rapids.sql.telemetry.samplePeriodMs": 20.0}),
        http_port=0)
    try:
        for _ in range(2):
            _run_q(1, tables, _kconf())
        slow = t.slow_query_log()
        assert slow
        entry = slow[0]
        assert "top_kernel" in entry, entry
        tk = entry["top_kernel"]
        assert len(tk["fingerprint"]) == 12
        assert 0 < tk["device_share_pct"] <= 100.0
        text = t.registry.prometheus_text()
        assert "tpu_rapids_kernel_device_seconds_total" in text
        assert "tpu_rapids_kernel_time_seconds_" in text
        assert "tpu_rapids_kernel_catalog_entries" in text
    finally:
        T.stop()


# ---------------------------------------------------------------------------
# the shared roofline source (satellite: one conf-overridable table)
def test_roofline_single_source_defaults():
    # the movement ledger's nominal table IS the roofline registry
    # defaults — they cannot diverge
    assert MV.NOMINAL_GBPS is RL.DEFAULT_EDGE_GBPS
    assert RL.edge_table(C.RapidsConf()) == RL.DEFAULT_EDGE_GBPS


def test_roofline_conf_overrides_flow_everywhere():
    conf = C.RapidsConf({
        "spark.rapids.sql.profile.roofline.wireGBps": 99.0,
        "spark.rapids.sql.profile.roofline.hbmGBps": 500.0,
        "spark.rapids.sql.profile.roofline.peakGflops": 1234.0})
    assert RL.edge_gbps("wire", conf) == 99.0
    assert RL.hbm_gbps(conf) == 500.0
    assert RL.peak_gflops(conf) == 1234.0
    # the movement report judges against the same override
    led = MV.DataMovementLedger("qtest", 0)
    led.record(MV.EDGE_WIRE, 10_000_000, site="send:loop")
    rep = led.report(1.0, conf=conf)
    assert rep["edges"]["wire"]["roofline_gbps"] == 99.0
    # the legacy all-edges override still wins over per-edge entries
    both = conf.set("spark.rapids.sql.profile.movement.rooflineGBps",
                    7.0)
    rep2 = led.report(1.0, float(
        both[C.MOVEMENT_ROOFLINE_GBPS]), conf=both)
    assert rep2["edges"]["wire"]["roofline_gbps"] == 7.0
    assert RL.edge_gbps("wire", both) == 7.0


def test_roofline_changes_kernel_report():
    KP.enable(_conf(**{
        "spark.rapids.sql.profile.kernels.enabled": True,
        "spark.rapids.sql.profile.kernels.sampleRate": 1}))
    kc = KernelCache(scope=("kp-unit-roofline",))
    fn = kc.get_or_build(
        ("k",), lambda: jax.jit(lambda x: (x * 2.0 + 1.0).sum()))
    led = KP.QueryKernelLedger("qtest", 0)
    x = jnp.ones((4096,), jnp.float32)
    fn(x)          # first: compile + cost capture
    for _ in range(4):
        out = fn(x)
        led.note(fn._kp_entry, 1_000_000)  # 1ms synthetic samples
    assert out is not None
    lo = led.report(C.RapidsConf({
        "spark.rapids.sql.profile.roofline.hbmGBps": 1000.0,
        "spark.rapids.sql.profile.roofline.peakGflops": 1e6}))
    hi = led.report(C.RapidsConf({
        "spark.rapids.sql.profile.roofline.hbmGBps": 1.0,
        "spark.rapids.sql.profile.roofline.peakGflops": 1.0}))
    assert lo[0]["roofline_pct"] < hi[0]["roofline_pct"]
