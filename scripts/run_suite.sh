#!/usr/bin/env bash
# CI suite runner (reference jenkins/spark-tests.sh analog): runs the
# fast unit tier, the scale ("slow") tier and a shim version matrix over
# the version-sensitive suites. Usage:
#   scripts/run_suite.sh [fast|slow|shims|all]
set -euo pipefail
cd "$(dirname "$0")/.."

TIER="${1:-fast}"
PYTEST=(python -m pytest -q -p no:randomly)

run_lint() {
  # static-analysis lane (budget <30s, no device/JAX needed): tpulint
  # enforces the engine invariants (host-sync accounting, semaphore
  # blocking discipline, bounded waits, conf registration, compile-
  # outside-the-lock) over the whole package, then the configs drift
  # gate proves docs/configs.md matches the registry.  The JSON run
  # feeds tooling; the summary line matches the other lanes.
  echo "== lint lane (tpulint engine invariants + configs drift gate) =="
  # JSON on stdout for tooling; the summary line rides stderr
  python scripts/lint.py --format json > /dev/null
  python scripts/gen_configs_doc.py --check
}

run_fast() {
  run_lint
  echo "== fast tier (unit + integration, virtual 8-device CPU mesh) =="
  "${PYTEST[@]}" tests/ -m "not slow" --ignore-glob="tests/test_workloads_*.py"
  echo "== workload parity (TPC-H / TPC-DS / TPCx-BB / Mortgage) =="
  "${PYTEST[@]}" tests/test_workloads_*.py
  run_oom_soak
  run_pipeline
  run_recovery
  run_watchdog
  run_profile
  run_movement
  run_concurrency
  run_fusion
  run_spmd
  run_speculation
  run_telemetry
  run_kernelprof
  run_residency
  run_oocore
}

run_residency() {
  # HBM residency lane: the ledger suite (provenance registration,
  # high-water reconciliation, leak detection, underflow guard, storm
  # isolation), then one profiled manager-lane q5 whose residency
  # report must show a NONZERO high-water mark with a peak composition
  # that sums to it and a clean leak verdict — the summary line
  # carries peak bytes, top site, and the verdict.
  echo "== residency lane (HBM provenance ledger, high-water marks, leak check) =="
  "${PYTEST[@]}" tests/test_residency.py
  python - <<'PYEOF'
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from spark_rapids_tpu import config as C
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables
from spark_rapids_tpu.utils import profile as P
from spark_rapids_tpu.utils import residency as RS

tables = gen_tables(np.random.default_rng(11), 1000)
run_query(5, tables, engine="tpu", conf=C.RapidsConf({
    **BENCH_CONF,
    "spark.rapids.sql.profile.enabled": True,
    "spark.rapids.shuffle.enabled": True,
    "spark.rapids.shuffle.localExecutors": 2}))
prof = P.last_profile()
res = prof.residency
assert res is not None and res["hbm_high_water"] > 0, res
comp = res["peak_composition"]
assert sum(comp.values()) == res["hbm_high_water"], comp
assert res["leaks"] == 0, res["leaked"]
assert res["live_end_bytes"] == 0, res
assert "-- residency --" in prof.explain()
assert RS.live_records_for_query(prof.query_id) == []
top = max(comp.items(), key=lambda kv: kv[1])
print("residency summary: q5 peak_mb=%.2f top_site=%s sites=%d "
      "allocs=%d leaks=%d verdict=clean" % (
          res["hbm_high_water"] / 1e6, top[0], len(comp),
          res["allocs"], res["leaks"]))
PYEOF
}

run_kernelprof() {
  # kernel-attribution lane: the kernelprof suite (disabled-path
  # parity, sampling, per-query isolation, catalog/cost capture,
  # roofline single-source), then one profiled q1
  # whose '-- kernels --' section must attribute the compute bucket —
  # the summary line carries coverage, top kernel, and roofline %.
  echo "== kernelprof lane (per-kernel device timing, cost/roofline attribution) =="
  "${PYTEST[@]}" tests/test_kernelprof.py
  python - <<'PYEOF'
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from pandas.testing import assert_frame_equal
from spark_rapids_tpu import config as C
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables
from spark_rapids_tpu.utils import kernelprof as KP
from spark_rapids_tpu.utils import profile as P

tables = gen_tables(np.random.default_rng(11), 20000)
off = C.RapidsConf(dict(BENCH_CONF))
on = C.RapidsConf({**BENCH_CONF,
    "spark.rapids.sql.pipeline.enabled": False,
    "spark.rapids.sql.profile.enabled": True,
    "spark.rapids.sql.profile.kernels.enabled": True,
    "spark.rapids.sql.profile.kernels.sampleRate": 1})
ref = run_query(1, tables, conf=off)
run_query(1, tables, conf=on)      # warm: first dispatches = compile
got = run_query(1, tables, conf=on)
assert_frame_equal(got.reset_index(drop=True),
                   ref.reset_index(drop=True))
prof = P.last_profile()
rows = prof.kernels
assert rows, "no kernel attribution rows"
assert "-- kernels --" in prof.explain()
kernel_ms = sum(r["device_ms"] for r in rows)
compute_ms = prof.breakdown["compute_s"] * 1e3
cov = kernel_ms / compute_ms if compute_ms else 0.0
roofed = [r for r in rows if "roofline_pct" in r]
assert roofed, "no kernel carried a cost/roofline join"
assert 0.35 <= cov <= 1.5, f"kernel/compute coverage wildly off: {cov}"
top = rows[0]
print("kernelprof summary: kernels=%d dispatches=%d kernel_ms=%.1f "
      "compute_ms=%.1f coverage=%.2f top=%s@%.1fms roofline=%.3f%% "
      "(%s-bound) catalog=%d" % (
          len(rows), sum(r["dispatches"] for r in rows), kernel_ms,
          compute_ms, cov, top["label"], top["device_ms"],
          top.get("roofline_pct") or 0.0, top.get("bound") or "?",
          KP.catalog_size()))
KP.reset()
PYEOF
}

run_spmd() {
  # SPMD whole-stage lane: the gang-execution suite (parity, ragged
  # partitions, deopt, ledger reconciliation), then a q1 parity smoke
  # over the 8-device mesh whose summary line carries the per-stage
  # dispatch counts — the O(partitions)->O(1) dispatch evidence.
  echo "== spmd lane (whole-mesh stage execution: parity + dispatch counts) =="
  "${PYTEST[@]}" tests/test_spmd.py
  python - <<'PYEOF'
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from pandas.testing import assert_frame_equal
from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec import spmd as SP
from spark_rapids_tpu.exec.scheduler import mesh_gate_stats
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables
from spark_rapids_tpu.parallel.mesh import active_mesh, make_mesh

tables = gen_tables(np.random.default_rng(11), 1000)
off = C.RapidsConf(dict(BENCH_CONF))
on = C.RapidsConf({**BENCH_CONF,
                   "spark.rapids.sql.spmd.enabled": True})
ref = run_query(1, tables, conf=off)
mesh = make_mesh(min(8, len(jax.devices())))
SP.reset_spmd_stats()
with active_mesh(mesh):
    for parts in (2, 8):
        got = run_query(1, tables, conf=on, num_partitions=parts)
        assert_frame_equal(got.reset_index(drop=True),
                           ref.reset_index(drop=True))
st = SP.spmd_stats()
assert st["gang_dispatches"] >= 2 and st["deopts"] == 0, st
gate = mesh_gate_stats()
print("spmd summary: q1 bit-exact spmd-vs-per-partition at 2 and 8 "
      "partitions; gang_dispatches=%d (one per stage) batches=%d "
      "slots=%d deopts=%d gate_dispatches=%d" % (
          st["gang_dispatches"], st["gang_batches"], st["gang_slots"],
          st["deopts"], gate["dispatches"]))
PYEOF
}

run_telemetry() {
  # engine-wide telemetry lane: the registry/exporter/sampler suite,
  # then one live smoke — a Prometheus scrape against the HTTP
  # endpoint WHILE a concurrent q1/q5 pair runs, asserting the
  # operator-facing gauges parse and the utilization timeline names
  # every sampled instant — with a busy-vs-idle summary line.
  echo "== telemetry lane (metrics registry, Prometheus export, utilization timeline) =="
  "${PYTEST[@]}" tests/test_telemetry.py
  python - <<'PYEOF'
import threading, time, urllib.request
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from spark_rapids_tpu import config as C
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables
from spark_rapids_tpu.utils import telemetry as T

t = T.start(C.RapidsConf({
    "spark.rapids.sql.telemetry.enabled": True,
    "spark.rapids.sql.telemetry.samplePeriodMs": 10.0}), http_port=0)
tables = gen_tables(np.random.default_rng(11), 1000)
conf = C.RapidsConf({**BENCH_CONF,
                     "spark.rapids.sql.profile.enabled": True})
for q in (1, 5):  # warm compiles outside the scraped window
    run_query(q, tables, conf=C.RapidsConf(dict(BENCH_CONF)))
errors = []
def worker(q):
    try:
        run_query(q, tables, conf=conf)
    except BaseException as e:
        errors.append((q, repr(e)))
ts = [threading.Thread(target=worker, args=(q,)) for q in (1, 5, 1, 5)]
[x.start() for x in ts]
scrapes = 0
url = "http://127.0.0.1:%d/metrics" % t.http_port
text = ""
while any(x.is_alive() for x in ts):
    text = urllib.request.urlopen(url, timeout=10).read().decode()
    scrapes += 1
    time.sleep(0.05)
[x.join(300) for x in ts]
assert not errors, errors
assert scrapes > 0 and "tpu_rapids_hbm_budget_bytes" in text
assert "tpu_rapids_semaphore_max_concurrent" in text
assert "tpu_rapids_scheduler_queue_depth" in text
assert "tpu_rapids_kernel_cache_entries" in text
util = t.utilization_summary()
named = sum(v for k, v in util.items() if k != "samples")
assert util["samples"] > 10 and named >= 99.0, util
slow = t.slow_query_log()
print("telemetry summary: scrapes=%d samples=%d util=%s "
      "slow_query_fingerprints=%d" % (
          scrapes, util["samples"],
          {k: v for k, v in util.items() if k != "samples"}, len(slow)))
T.stop()
PYEOF
}

run_speculation() {
  # tail-tolerance lane: the speculation/hedging/replication suite
  # (first-wins races, loser cancellation, replica promotion, spill
  # corruption, wire:wasted honesty), then an injected straggler run
  # whose summary line carries the speculation/hedge/replication
  # counters — the p95 trajectory's round-to-round evidence.
  echo "== speculation lane (stragglers, hedged fetches, replication) =="
  "${PYTEST[@]}" tests/test_speculation.py
  python - <<'PYEOF'
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, pandas as pd
from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec.basic import LocalBatchSource
from spark_rapids_tpu.exec.speculation import speculation_stats
from spark_rapids_tpu.exprs.base import col
from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
from spark_rapids_tpu.utils.watchdog import slow_injection_counts

conf = C.RapidsConf({
    "spark.rapids.shuffle.enabled": True,
    "spark.rapids.shuffle.localExecutors": 3,
    "spark.rapids.shuffle.replication.factor": 2,
    "spark.rapids.shuffle.hedge.enabled": True,
    "spark.rapids.shuffle.hedge.delayMs": 40.0,
    "spark.rapids.sql.speculation.enabled": True,
    "spark.rapids.sql.speculation.minTaskRuntimeMs": 50.0,
    "spark.rapids.sql.speculation.minCompletedTasks": 1,
    "spark.rapids.sql.watchdog.pollInterval": 0.05,
    "spark.rapids.memory.faultInjection.slowSite": "map-task",
    "spark.rapids.memory.faultInjection.slowFactor": 10.0,
    "spark.rapids.memory.faultInjection.slowUnitMs": 40.0,
    "spark.rapids.memory.faultInjection.slowVictim": "local-1",
    "spark.rapids.memory.faultInjection.slowSeed": 11,
})
rng = np.random.default_rng(7)
df = pd.DataFrame({"k": rng.integers(0, 50, 4000).astype(np.int64),
                   "v": rng.integers(0, 10**6, 4000).astype(np.int64)})
with C.session(conf):
    src = LocalBatchSource.from_pandas(df, num_partitions=4)
    ex = ShuffleExchangeExec(HashPartitioning([col("k")], 3), src)
    rows = sum(b.num_rows for it in ex.execute_partitions() for b in it)
assert rows == len(df), f"row loss under slow injection: {rows}"
m = ex.metrics.as_dict()
s = speculation_stats()
print("speculation summary: rows=%d spec_tasks=%d spec_wins=%d "
      "losers_cancelled=%d hedged=%d hedged_wins=%d replicated_mb=%.2f "
      "slow_units=%s" % (
          rows, m.get("numSpeculativeTasks", 0),
          m.get("numSpeculativeWins", 0), s["losers_cancelled"],
          m.get("numHedgedFetches", 0), m.get("numHedgedWins", 0),
          m.get("replicatedBytes", 0) / 1e6, slow_injection_counts()))
assert m.get("numSpeculativeWins", 0) > 0, m
PYEOF
}

run_movement() {
  # data-movement lane: the ledger suite (edge conservation, spill
  # reconciliation, disabled-path parity, per-query isolation), then
  # TPC-H q1/q5 movement-report validation — q5 through the manager
  # shuffle lane (2 in-process executors + seeded OOM injection) must
  # report upload/readback/spill/wire traffic with wire-conservation
  # (bytes served == bytes assembled) holding — and a per-edge summary
  # line with effective GB/s.
  echo "== movement lane (per-query data-movement ledger, roofline) =="
  "${PYTEST[@]}" tests/test_movement.py
  python - <<'PYEOF'
import jax
jax.config.update("jax_platforms", "cpu")
import json
import numpy as np
from spark_rapids_tpu import config as C
from spark_rapids_tpu.memory import retry as R
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables
from spark_rapids_tpu.utils import profile as P

tables = gen_tables(np.random.default_rng(11), 1000)
for q, extra in ((1, {}), (5, {
        "spark.rapids.shuffle.enabled": True,
        "spark.rapids.shuffle.localExecutors": 2,
        "spark.rapids.memory.faultInjection.oomRate": 0.5,
        "spark.rapids.memory.faultInjection.seed": 7,
        "spark.rapids.memory.faultInjection.maxInjections": 16})):
    R.reset_oom_injection()
    run_query(q, tables, engine="tpu", conf=C.RapidsConf({
        **BENCH_CONF, "spark.rapids.sql.profile.enabled": True, **extra}))
    R.reset_oom_injection()
    prof = P.last_profile()
    mv = prof.movement
    assert mv is not None and mv["total_bytes"] > 0, mv
    edges = mv["edges"]
    if q == 5:
        for e in ("upload", "readback", "wire"):
            assert edges[e]["bytes"] > 0, (e, edges[e])
        sites = edges["wire"]["sites"]
        sent = sum(v["bytes"] for s, v in sites.items()
                   if s.startswith("send"))
        recv = sum(v["bytes"] for s, v in sites.items()
                   if s.startswith("recv"))
        assert sent == recv > 0, (sent, recv)
    assert "-- data movement --" in prof.explain()
    counters = [e for e in prof.chrome_trace()["traceEvents"]
                if e["ph"] == "C"]
    assert counters, "no Perfetto counter tracks"
    print("movement summary: q%d total_mb=%.2f %s" % (
        q, mv["total_bytes"] / 1e6,
        " ".join("%s=%.2fMB@%.3fGB/s" % (
            e, d["bytes"] / 1e6, d["gbps_avg"])
            for e, d in edges.items() if d["bytes"])))
PYEOF
}

run_fusion() {
  # whole-stage fusion lane: the fusion suite (composition, CSE,
  # per-member metrics, KernelCache bound), then TPC-H q1/q5 parity
  # with fusion ON vs OFF (bit-exact), and a deopt check — a query
  # mixing supported + unsupported (ANSI-cast) expressions must run
  # with only the affected stage unfused, never error.
  echo "== fusion lane (whole-stage XLA fusion parity + deopt) =="
  "${PYTEST[@]}" tests/test_fusion.py
  python - <<'PYEOF'
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from pandas.testing import assert_frame_equal
from spark_rapids_tpu import config as C
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables

tables = gen_tables(np.random.default_rng(11), 1000)
on = C.RapidsConf(dict(BENCH_CONF))
off = C.RapidsConf({**BENCH_CONF,
                    "spark.rapids.sql.fusion.enabled": False})
for q in (1, 5):
    a = run_query(q, tables, conf=on)
    b = run_query(q, tables, conf=off)
    assert_frame_equal(a.reset_index(drop=True),
                       b.reset_index(drop=True))
from spark_rapids_tpu.exec.base import (kernel_cache_evictions,
                                        kernel_cache_size)
print("fusion summary: q1/q5 bit-exact fused-vs-unfused "
      "kernel_cache_size=%d evictions=%d" % (
          kernel_cache_size(), kernel_cache_evictions()))
PYEOF
}

run_concurrency() {
  # multi-query serving lane: the scheduler suite (admission control,
  # fair-share semaphore, cross-query fault isolation, result cache),
  # then a 4-thread mixed q1/q5 storm with seeded OOM injection aimed
  # at ONE victim session — every result bit-exact vs serial, zero
  # leaked permits/admissions/producers — with a metrics summary line.
  echo "== concurrency lane (admission control, fair share, fault isolation) =="
  "${PYTEST[@]}" tests/test_scheduler.py
  python - <<'PYEOF'
import threading
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from pandas.testing import assert_frame_equal
from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec.scheduler import scheduler_stats
from spark_rapids_tpu.memory.device_manager import DeviceManager
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables

tables = gen_tables(np.random.default_rng(11), 1000)
clean = C.RapidsConf(dict(BENCH_CONF))
victim = C.RapidsConf({**BENCH_CONF,
    "spark.rapids.memory.faultInjection.oomRate": 1.0,
    "spark.rapids.memory.faultInjection.seed": 13,
    "spark.rapids.memory.faultInjection.maxInjections": 16})
ref = {q: run_query(q, tables, conf=clean) for q in (1, 5)}
results, errors = {}, []
def worker(i, q, conf):
    try:
        results[i] = (q, run_query(q, tables, conf=conf))
    except BaseException as e:
        errors.append((i, q, repr(e)))
mix = [(1, victim), (5, clean), (1, clean), (5, clean)]
ts = [threading.Thread(target=worker, args=(i, q, conf))
      for i, (q, conf) in enumerate(mix)]
[t.start() for t in ts]; [t.join(300) for t in ts]
assert not errors, errors
for i, (q, df) in results.items():
    assert_frame_equal(df.reset_index(drop=True),
                       ref[q].reset_index(drop=True))
snap = TpuSemaphore.get().snapshot()
assert snap["refs"] == {}, snap
dm = DeviceManager.get()
assert dm.admissions() == {} and dm.reserved_bytes == 0
st = scheduler_stats()
print("concurrency summary: queries=%d bit_exact=ok admitted=%d "
      "queued=%d rejected=%d longest_queue_wait_ms=%d "
      "sem_longest_wait_ms=%d sem_waits=%d" % (
          len(results), st["admitted"], st["queued"], st["rejected"],
          st["longest_queue_wait_ms"], snap["longestWaitMs"],
          snap["waitCount"]))
PYEOF
}

run_profile() {
  # observability lane: TPC-H q1/q5 with per-query profiling on must
  # yield a Perfetto-parseable Chrome trace with a deep multi-thread
  # span tree, an EXPLAIN-with-metrics report where every node carries
  # resolved counters, and a correlated JSONL event log — then print
  # the wall-clock breakdown as the lane's summary line.
  echo "== profile lane (span tracing, Chrome trace, EXPLAIN-with-metrics) =="
  "${PYTEST[@]}" tests/test_profile.py
  python - <<'PYEOF'
import json
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from spark_rapids_tpu import config as C
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables
from spark_rapids_tpu.utils import profile as P

tables = gen_tables(np.random.default_rng(11), 1000)
conf = C.RapidsConf({**BENCH_CONF,
                     "spark.rapids.sql.profile.enabled": True})
for q in (1, 5):
    run_query(q, tables, engine="tpu", conf=conf)
    prof = P.last_profile()
    trace = json.loads(json.dumps(prof.chrome_trace()))  # must parse
    threads = {e["tid"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert prof.span_depth() >= 4, prof.span_depth()
    assert len(threads) >= 3, threads
    assert all(ln.rstrip().endswith("]")
               for ln in prof.plan_report.splitlines()), "unannotated node"
    assert {e["query_id"] for e in prof.events} == {prof.query_id}
    print("profile summary: q%d wall_ms=%.1f spans=%d depth=%d "
          "threads=%d events=%d breakdown=%s" % (
              q, prof.wall_s * 1e3, len(prof.spans), prof.span_depth(),
              len(threads), len(prof.events),
              json.dumps(prof.breakdown)))
PYEOF
}

run_watchdog() {
  # liveness lane: every seeded hang site (producer, collective,
  # shuffle-server, pyudf, compile) must end in a descriptive
  # TpuQueryTimeout + diagnostic dump within ~2x its deadline — never
  # a hang, never leaked permits/threads — and the process must run a
  # clean bit-exact query afterwards.  The summary line reports the
  # timeout/cancel metrics of one injected query.
  echo "== watchdog lane (seeded hang injection, deadlines, cancellation) =="
  "${PYTEST[@]}" tests/test_watchdog.py
  python - <<'PYEOF'
import time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from spark_rapids_tpu import config as C
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables
from spark_rapids_tpu.plan.overrides import ExecutionPlanCapture
from spark_rapids_tpu.utils import watchdog as W

tables = gen_tables(np.random.default_rng(11), 500)
conf = C.RapidsConf({**BENCH_CONF,
    "spark.rapids.memory.faultInjection.hangSite": "producer",
    "spark.rapids.memory.faultInjection.hangAfterBatches": 1,
    "spark.rapids.sql.watchdog.taskTimeout": 2.0,
    "spark.rapids.sql.watchdog.pollInterval": 0.1})
t0 = time.monotonic()
try:
    run_query(1, tables, engine="tpu", conf=conf)
    raise SystemExit("hang injection did not cancel the query")
except W.TpuQueryTimeout:
    pass
el = time.monotonic() - t0
m = ExecutionPlanCapture.last_plan.metrics.as_dict()
print("watchdog summary: cancelled_in=%.1fs timeouts=%d cancels=%d "
      "dumps=%d slowest_heartbeat_ms=%d" % (
          el, m.get("numWatchdogTimeouts", 0), m.get("numCancels", 0),
          m.get("watchdogDumps", 0), m.get("slowestHeartbeatMs", 0)))
W.reset_hang_injection()
PYEOF
}

run_recovery() {
  # shuffle fault-recovery lane: seeded peer_kill injection (the victim
  # executor goes dark mid-stream on both transport lanes) must yield
  # bit-exact results via map recomputation + bounded stage retries —
  # plus epoch staleness, blacklist decay, and exhaustion (raise, never
  # hang) coverage.  The summary line reports the recovery metrics of
  # one injected exchange, like the oom/pipeline/bench summaries.
  echo "== shuffle recovery lane (seeded peer-kill injection, bounded stage retries) =="
  "${PYTEST[@]}" tests/test_shuffle_recovery.py
  python - <<'PYEOF'
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, pandas as pd
from spark_rapids_tpu import config as C
from spark_rapids_tpu.exec.basic import LocalBatchSource
from spark_rapids_tpu.exprs.base import col
from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
from spark_rapids_tpu.shuffle.partitioning import HashPartitioning

conf = C.RapidsConf({
    "spark.rapids.shuffle.enabled": True,
    "spark.rapids.shuffle.localExecutors": 2,
    "spark.rapids.shuffle.bounceBuffers.size": 2048,
    "spark.rapids.shuffle.fetch.maxRetries": 1,
    "spark.rapids.shuffle.fetch.backoff.baseMs": 1.0,
    "spark.rapids.shuffle.recovery.blacklist.failureThreshold": 1,
    "spark.rapids.shuffle.transport.faultInjection.peerKillAfterFrames": 3,
})
rng = np.random.default_rng(7)
df = pd.DataFrame({"k": rng.integers(0, 50, 4000).astype(np.int64),
                   "v": rng.integers(0, 10**6, 4000).astype(np.int64)})
with C.session(conf):
    src = LocalBatchSource.from_pandas(df, num_partitions=4)
    ex = ShuffleExchangeExec(HashPartitioning([col("k")], 3), src)
    rows = sum(b.num_rows for it in ex.execute_partitions() for b in it)
assert rows == len(df), f"row loss under injection: {rows}"
m = ex.metrics.as_dict()
print("recovery summary: rows=%d fetch_failures=%d map_recomputes=%d "
      "stage_retries=%d peers_blacklisted=%d recovery_ms=%.1f" % (
          rows, m.get("numFetchFailures", 0),
          m.get("numMapRecomputes", 0), m.get("numStageRetries", 0),
          m.get("numPeersBlacklisted", 0),
          m.get("recoveryTime", 0) / 1e6))
PYEOF
}

run_pipeline() {
  # async-pipeline lane: the parity suites must be bit-identical with
  # bounded prefetch ON (depth 2) and fully OFF — the overlap layer may
  # move work across threads but never change a result.  Env overrides
  # flip the conf defaults suite-wide (config.py PIPELINE_* entries).
  echo "== pipeline lane (prefetchDepth=2 vs pipelining disabled) =="
  SPARK_RAPIDS_TPU_PIPELINE=1 SPARK_RAPIDS_TPU_PIPELINE_DEPTH=2 \
    "${PYTEST[@]}" tests/test_pipeline.py tests/test_tpch.py
  SPARK_RAPIDS_TPU_PIPELINE=0 \
    "${PYTEST[@]}" tests/test_pipeline.py tests/test_tpch.py
}

run_oom_soak() {
  # the retry/split/fallback lattice must run on EVERY suite invocation,
  # not just when a real TPU OOMs: seeded reservation fault injection +
  # a tiny accounted HBM budget (conf overrides inside the suite) drive
  # spill, batch splitting, floor fallback, and the semaphore
  # release/reacquire path on the CPU mesh.  OOM_SOAK=1 widens the
  # seed sweep beyond the default single pass.
  echo "== OOM soak lane (seeded reservation fault injection, tiny HBM budget) =="
  SPARK_RAPIDS_TPU_OOM_SOAK="${SPARK_RAPIDS_TPU_OOM_SOAK:-1}" \
    "${PYTEST[@]}" tests/test_oom_retry.py -m "not slow"
}

run_oocore() {
  # out-of-core lane: the bounded-HBM degradation suite (external
  # sort / grace join / agg spill bit-exactness, ledger reconciliation,
  # corruption recovery, watchdog-covered merge passes, the chaos
  # composite soak including the slow q5 leg), then one TPC-H q5 run
  # under a budget a fraction of its working set with spill-corruption
  # injection lit — bit-exact vs the unconstrained lane, overflow bytes
  # proven onto the movement ledger's oocore spill edges, zero leaked
  # buffers/admissions/reservations — with a spill-traffic summary line.
  echo "== out-of-core lane (bounded-HBM external sort/join/agg, spill-tier streaming) =="
  "${PYTEST[@]}" tests/test_out_of_core.py
  python - <<'PYEOF'
import jax
jax.config.update("jax_platforms", "cpu")
import tempfile
import numpy as np
from pandas.testing import assert_frame_equal
from spark_rapids_tpu import config as C
from spark_rapids_tpu.memory import ResourceEnv
from spark_rapids_tpu.memory import oocore as OC
from spark_rapids_tpu.memory import retry as R
from spark_rapids_tpu.memory import stores as ST
from spark_rapids_tpu.models.tpch_bench import BENCH_CONF, run_query
from spark_rapids_tpu.models.tpch_data import gen_tables
from spark_rapids_tpu.utils import movement as MV
from spark_rapids_tpu.utils import profile as P

tables = gen_tables(np.random.default_rng(11), 3000)
ref = run_query(5, tables, conf=C.RapidsConf(dict(BENCH_CONF)))
conf = C.RapidsConf({**BENCH_CONF,
    "spark.rapids.sql.profile.enabled": True,
    "spark.rapids.memory.hbmBudgetBytes": 1 << 14,
    "spark.rapids.memory.host.spillStorageSize": 1 << 14,
    "spark.rapids.memory.faultInjection.spillCorruptRate": 0.005,
    "spark.rapids.memory.faultInjection.seed": 7,
    "spark.rapids.memory.oocore.runReplicas": 2,
    "spark.rapids.memory.gpu.allocFraction": 1.0,
    "spark.rapids.memory.gpu.reserve": 0})
C.set_active_conf(conf)
env = ResourceEnv.init(hbm_total=1 << 26,
                       spill_dir=tempfile.mkdtemp())
R.reset_oom_injection()
ST.reset_spill_corruption()
OC.reset_run_accounting()
got = run_query(5, tables, conf=conf)
assert_frame_equal(got.reset_index(drop=True),
                   ref.reset_index(drop=True), check_exact=True)
prof = P.last_profile()
sites = prof.movement["edges"][MV.EDGE_SPILL]["sites"]
oocore_mb = sum(v["bytes"] for s, v in sites.items()
                if s.startswith(OC.SITE_PREFIX)) / 1e6
assert abs(oocore_mb * 1e6 - OC.run_bytes_spilled()) < 1, \
    (oocore_mb, OC.run_bytes_spilled())
assert prof.oocore is not None, "profile lost the out-of-core section"
dm = env.device_manager
assert len(env.catalog) == 0, "leaked buffers"
assert dm.admissions() == {} and dm.reserved_bytes == 0
assert env.disk_store.orphaned_spill_files() == []
tot = prof.oocore["totals"]
print("oocore summary: q5 bit-exact under %dKB budget; runs=%d "
      "spill_mb=%.2f merge_passes=%d grace_partitions=%d "
      "corruptions_injected=%d recovered=%d leaks=0" % (
          (1 << 14) // 1024, OC.runs_spilled(), oocore_mb,
          tot["merge_passes"], tot["grace_partitions"],
          ST.injected_spill_corruptions(),
          tot["corrupt_recovered"]))
ResourceEnv.shutdown()
PYEOF
}

run_slow() {
  echo "== slow tier (multi-batch scale + asserted spill) =="
  "${PYTEST[@]}" tests/test_scale_workloads.py -m slow
}

run_shims() {
  # the shim suite internally parametrizes the full version matrix
  # (3.0.0 / 3.0.1 / 3.0.2 / 3.1.0 / databricks) via
  # spark.rapids.tpu.sparkVersion — the per-version premerge analog
  # (reference jenkins/Jenkinsfile.30*)
  echo "== shim version matrix =="
  "${PYTEST[@]}" tests/test_shims.py tests/test_plan_overrides.py
}

case "$TIER" in
  lint)     run_lint ;;
  fast)     run_fast ;;
  slow)     run_slow ;;
  shims)    run_shims ;;
  oom)      run_oom_soak ;;
  pipeline) run_pipeline ;;
  recovery) run_recovery ;;
  watchdog) run_watchdog ;;
  profile)  run_profile ;;
  movement) run_movement ;;
  concurrency) run_concurrency ;;
  fusion)   run_fusion ;;
  spmd)     run_spmd ;;
  speculation) run_speculation ;;
  telemetry) run_telemetry ;;
  kernelprof) run_kernelprof ;;
  residency) run_residency ;;
  oocore)   run_oocore ;;
  all)      run_fast; run_slow; run_shims ;;
  *) echo "usage: $0 [lint|fast|slow|shims|oom|pipeline|recovery|watchdog|profile|movement|concurrency|fusion|spmd|speculation|telemetry|kernelprof|residency|oocore|all]" >&2
     exit 2 ;;
esac
