"""Typed configuration registry.

Mirrors the reference's `RapidsConf.scala` (SURVEY.md §2.14): typed entries
with defaults, per-operator auto-derived enable keys, and self-documenting
`help()` output that generates docs/configs.md.  Keys keep the
`spark.rapids.*` naming so users of the reference find the same surface.
"""
from __future__ import annotations

import dataclasses
import threading
from contextlib import contextmanager
from typing import Any, Callable, Optional

_REGISTRY: dict[str, "ConfEntry"] = {}


@dataclasses.dataclass
class ConfEntry:
    key: str
    default: Any
    doc: str
    converter: Callable[[str], Any]
    internal: bool = False

    def get(self, conf: "RapidsConf") -> Any:
        return conf.get(self.key, self.default)


def _register(entry: ConfEntry) -> ConfEntry:
    _REGISTRY[entry.key] = entry
    return entry


def _bool(s):
    return s if isinstance(s, bool) else str(s).lower() in ("true", "1", "yes")


def conf(key: str, default: Any, doc: str, internal: bool = False) -> ConfEntry:
    conv = {bool: _bool, int: int, float: float, str: str}[type(default)]
    return _register(ConfEntry(key, default, doc, conv, internal))


# --- core enables (reference RapidsConf.scala:271-690) ----------------------
SQL_ENABLED = conf("spark.rapids.sql.enabled", True,
                   "Enable or disable TPU SQL acceleration entirely.")
EXPLAIN = conf("spark.rapids.sql.explain", "NONE",
               "Explain why parts of a plan were not placed on the TPU: "
               "NONE, NOT_ON_GPU, ALL.")
INCOMPATIBLE_OPS = conf("spark.rapids.sql.incompatibleOps.enabled", False,
                        "Enable operators producing results that differ "
                        "slightly from Spark (e.g. float aggregation order).")
VARIABLE_FLOAT_AGG = conf("spark.rapids.sql.variableFloatAgg.enabled", False,
                          "Allow float aggregations whose result can vary "
                          "with evaluation order.")
CASTS_FLOAT_TO_STRING = conf("spark.rapids.sql.castFloatToString.enabled",
                             False, "Enable float->string cast (formatting "
                             "differs slightly from Spark).")
CASTS_STRING_TO_FLOAT = conf("spark.rapids.sql.castStringToFloat.enabled",
                             False, "Enable string->float cast.")
CASTS_STRING_TO_TS = conf("spark.rapids.sql.castStringToTimestamp.enabled",
                          False, "Enable string->timestamp cast.")
REPLACE_SORT_MERGE_JOIN = conf(
    "spark.rapids.sql.replaceSortMergeJoin.enabled", True,
    "Replace SortMergeJoin with a TPU shuffled hash join.")
TEST_ENABLED = conf("spark.rapids.sql.test.enabled", False,
                    "Testing hook: fail if an op expected on TPU falls back.",
                    internal=True)
TEST_ALLOWED_NONGPU = conf("spark.rapids.sql.test.allowedNonGpu", "",
                           "Comma-separated ops allowed on CPU in test mode.",
                           internal=True)
EXPORT_COLUMNAR_RDD = conf("spark.rapids.sql.exportColumnarRdd", False,
                           "Expose the final columnar output for ML "
                           "integration (ColumnarRdd).")
SPARK_VERSION = conf("spark.rapids.tpu.sparkVersion", "3.0.1",
                     "Spark version the session emulates; selects the "
                     "shim set (reference ShimLoader.scala:26-61).")
ALLOW_UNKNOWN_SPARK_VERSION = conf(
    "spark.rapids.tpu.allowUnknownSparkVersion", False,
    "When no shim matches the Spark version exactly, fall back to the "
    "nearest same-minor shim with a warning instead of failing "
    "(default: fail, like the reference ShimLoader).")
MAX_BATCH_ROWS = conf("spark.rapids.tpu.batchMaxRows", 65536,
                      "Row cap per device batch at upload/scan/coalesce "
                      "boundaries.  Bounds the set of compiled kernel "
                      "shapes: every operator compiles at a few bucketed "
                      "capacities <= this and streams larger data as "
                      "multiple batches (XLA:TPU sort compile time grows "
                      "steeply with capacity).")
PRUNE_COLUMNS = conf("spark.rapids.tpu.columnPruning.enabled", True,
                     "Prune unreferenced columns at scan/source leaves "
                     "before plan rewrite (the role Catalyst's "
                     "ColumnPruning plays for the reference).")

# --- batch sizing / memory (reference :271-360) -----------------------------
BATCH_SIZE_BYTES = conf("spark.rapids.sql.batchSizeBytes", 2147483136,
                        "Target device batch size in bytes for coalescing.")
MAX_READER_BATCH_ROWS = conf("spark.rapids.sql.reader.batchSizeRows",
                             2147483647, "Max rows per scan batch.")
MAX_READER_BATCH_BYTES = conf("spark.rapids.sql.reader.batchSizeBytes",
                              2147483136, "Soft max bytes per scan batch.")
CONCURRENT_TPU_TASKS = conf("spark.rapids.sql.concurrentGpuTasks", 1,
                            "Number of tasks that may hold the accelerator "
                            "concurrently (GpuSemaphore analog).")
HBM_ALLOC_FRACTION = conf("spark.rapids.memory.gpu.allocFraction", 0.9,
                          "Fraction of HBM to dedicate to the arena pool.")
HBM_RESERVE = conf("spark.rapids.memory.gpu.reserve", 1073741824,
                   "HBM bytes kept free for XLA scratch/fusion temporaries.")
HBM_BUDGET_BYTES = conf(
    "spark.rapids.memory.hbmBudgetBytes", 0,
    "Hard cap (bytes) on the accounted HBM arena budget, applied AFTER "
    "the allocFraction/reserve arithmetic: the effective budget is "
    "min(total*allocFraction - reserve, this).  0 (default) disables "
    "the cap.  This is the out-of-core lever: capping the budget below "
    "an operator's working set makes DeviceManager.try_reserve report "
    "no headroom, which routes sort/join/aggregate through their "
    "external (spill-backed) algorithms instead of split-retrying to "
    "the row floor — bounded-HBM execution on data larger than device "
    "memory.")
HOST_SPILL_STORAGE = conf("spark.rapids.memory.host.spillStorageSize",
                          1073741824, "Host memory for spilled device data.")
RETRY_MIN_SPLIT_ROWS = conf(
    "spark.rapids.memory.retry.minSplitRows", 1024,
    "Floor for OOM split-and-retry: a batch at or below this many rows "
    "is not subdivided further; reservation failure there takes the "
    "retry.fallback path instead (memory/retry.py harness; the role of "
    "the reference's SplitAndRetryOOM minimum split size).")
RETRY_FALLBACK = conf(
    "spark.rapids.memory.retry.fallback", "bestEffort",
    "What happens when a batch at the minimum split size still cannot "
    "be reserved: bestEffort runs it unreserved (the accounted arena "
    "is advisory; a true device OOM surfaces as an XLA allocation "
    "error), error fails the query with an actionable message.  Never "
    "a silent wrong answer.")
OOM_INJECT_RATE = conf(
    "spark.rapids.memory.faultInjection.oomRate", 0.0,
    "TEST ONLY: probability that a device-memory reservation is forced "
    "to fail, exercising the spill -> retry -> split-and-retry -> "
    "floor-fallback lattice on CPU CI without a real HBM-sized "
    "workload (the memory-layer sibling of the shuffle transport "
    "fault injector).  0 disables injection.")
OOM_INJECT_SEED = conf(
    "spark.rapids.memory.faultInjection.seed", 0,
    "Deterministic seed for OOM fault injection.")
OOM_INJECT_MAX = conf(
    "spark.rapids.memory.faultInjection.maxInjections", 1024,
    "Hard cap on injected reservation failures per injector lifetime, "
    "guaranteeing forward progress in soak loops even at oomRate=1.0 "
    "(0 = unlimited).")

# --- query watchdog (utils/watchdog.py) --------------------------------------
WATCHDOG_ENABLED = conf(
    "spark.rapids.sql.watchdog.enabled", True,
    "Detect hung queries: every long-lived activity (prefetch "
    "producers, shuffle servers and fetch loops, collective-exchange "
    "dispatches, AQE stage fills, pyudf workers, XLA compiles) "
    "registers a progress heartbeat; a scanner thread that sees no "
    "progress past the activity's deadline class emits one diagnostic "
    "dump and cancels the query cooperatively, raising a descriptive "
    "TpuQueryTimeout instead of hanging forever.  The liveness analog "
    "of Spark's task-level speculation/kill machinery, which a "
    "standalone engine otherwise lacks.")
WATCHDOG_POLL_INTERVAL = conf(
    "spark.rapids.sql.watchdog.pollInterval", 1.0,
    "Seconds between watchdog scans of registered heartbeats.  Bounds "
    "detection latency at deadline + pollInterval; lower values only "
    "matter with sub-second deadlines (tests).")
WATCHDOG_TASK_TIMEOUT = conf(
    "spark.rapids.sql.watchdog.taskTimeout", 300.0,
    "Deadline (seconds) for task-class activities: prefetch producer "
    "loops, shuffle server/fetch handlers, AQE stage fills, pyudf "
    "workers.  An activity making no progress for this long is "
    "declared hung and the query is cancelled with a diagnostic dump.")
WATCHDOG_COLLECTIVE_TIMEOUT = conf(
    "spark.rapids.sql.watchdog.collectiveTimeout", 120.0,
    "Deadline (seconds) for collective-class activities (ICI "
    "all-to-all exchange dispatches).  Collectives block ALL mesh "
    "participants when one goes dark, so their deadline is tighter "
    "than the task class.")
WATCHDOG_COMPILE_TIMEOUT = conf(
    "spark.rapids.sql.watchdog.compileTimeout", 600.0,
    "Deadline (seconds) for XLA kernel compiles (and single-flight "
    "waiters parked on another thread's compile).  Sort-heavy shapes "
    "legitimately compile for minutes; raise this before blaming a "
    "pathological compile.")
WATCHDOG_DUMP_ON_TIMEOUT = conf(
    "spark.rapids.sql.watchdog.dumpOnTimeout", True,
    "Emit one diagnostic dump (all thread stacks, semaphore holders, "
    "prefetch queue stats, in-flight shuffle fetches, hang-injection "
    "state) when the watchdog declares a timeout; the dump rides on "
    "the raised TpuQueryTimeout and is logged at ERROR.")
HANG_INJECT_SITE = conf(
    "spark.rapids.memory.faultInjection.hangSite", "",
    "TEST ONLY: inject a hang at the named site so watchdog "
    "detection, cancellation, and resource release are testable "
    "without a real dead peer or wedged compile.  Sites: producer "
    "(prefetch producer loop), collective (mesh exchange dispatch), "
    "shuffle-server (chunk emit stall), pyudf (wedged UDF worker), "
    "compile (KernelCache builder).  The injected hang blocks until "
    "the query's CancelToken fires — like a Spark task kill, "
    "cancellation is cooperative.  Empty disables.", internal=True)
HANG_INJECT_AFTER = conf(
    "spark.rapids.memory.faultInjection.hangAfterBatches", 0,
    "TEST ONLY: the injected hang engages after this many units of "
    "progress (batches produced, chunks served, compiles started) at "
    "the configured hangSite.", internal=True)
SLOW_INJECT_SITE = conf(
    "spark.rapids.memory.faultInjection.slowSite", "",
    "TEST ONLY: inject a seeded delay at the named site so the "
    "tail-tolerance layer (speculation, hedged fetches) is testable "
    "without a real degraded peer — the *slow* sibling of the "
    "kill/hang/corrupt injectors.  Sites: map-task (per batch of a "
    "manager-lane map task), shuffle-server (per served buffer).  The "
    "delay is cancellable (a losing speculative/hedged attempt parked "
    "in it wakes immediately on cancellation).  Empty disables.",
    internal=True)
SLOW_INJECT_FACTOR = conf(
    "spark.rapids.memory.faultInjection.slowFactor", 0.0,
    "TEST ONLY: slowdown multiplier for slowSite — each unit of work "
    "at the site sleeps (slowFactor - 1) x slowUnitMs, so factor 10 "
    "models a peer running 10x slower than nominal.  <= 1 disables.",
    internal=True)
SLOW_INJECT_SEED = conf(
    "spark.rapids.memory.faultInjection.slowSeed", 0,
    "TEST ONLY: seed for the slow injector's +/-25% delay jitter — "
    "deterministic straggler schedules in soak tests.", internal=True)
SLOW_INJECT_VICTIM = conf(
    "spark.rapids.memory.faultInjection.slowVictim", "",
    "TEST ONLY: executor id the slow injector targets (e.g. "
    "'local-1'); empty slows every executor that reaches the site.",
    internal=True)
SLOW_INJECT_UNIT_MS = conf(
    "spark.rapids.memory.faultInjection.slowUnitMs", 20.0,
    "TEST ONLY: nominal per-unit work time (ms) the slowFactor "
    "multiplies — the injected delay per batch/buffer is "
    "(slowFactor - 1) x this.", internal=True)
SPILL_CORRUPT_RATE = conf(
    "spark.rapids.memory.faultInjection.spillCorruptRate", 0.0,
    "TEST ONLY: probability that a freshly written spill file has one "
    "payload byte flipped on disk (after the CRC frame was written), "
    "proving the disk re-read's integrity check surfaces "
    "SpillCorruptionError instead of deserializing garbage.  Seeded "
    "by faultInjection.seed.  0 disables.", internal=True)

# --- out-of-core execution (memory/oocore.py) --------------------------------
OOCORE_ENABLED = conf(
    "spark.rapids.memory.oocore.enabled", True,
    "Degrade gracefully to external algorithms when an operator's "
    "working set exceeds the HBM budget's headroom: sort spills sorted "
    "runs and k-way merges them back in budget-sized windows, hash "
    "join grace-partitions the build AND probe sides by key hash and "
    "joins partition pairs that fit, and hash aggregate spills partial "
    "group state and re-merges it.  Runs travel the existing "
    "device->host->disk spill tiers (every hop on the movement "
    "ledger's spill edges).  OOM split-and-retry remains the inner "
    "lattice; out-of-core is the outer ring engaged BEFORE the "
    "retry.fallback path.  Off: the pre-out-of-core behavior (split "
    "to minSplitRows, then bestEffort|error).")
OOCORE_WINDOW_FRACTION = conf(
    "spark.rapids.memory.oocore.windowFraction", 0.5,
    "Fraction of the HBM budget one operator may hold resident before "
    "degrading to its external algorithm — and the size of each merge "
    "window when it does.  The working-set estimate is real "
    "accounting (2x device batch bytes, the same estimate the OOM "
    "harness reserves with) judged against try_reserve headroom, not "
    "a guess.  Smaller values spill earlier and merge in more passes; "
    "larger values risk the inner retry lattice engaging first.")
OOCORE_GRACE_PARTITIONS = conf(
    "spark.rapids.memory.oocore.gracePartitions", 8,
    "Fan-out of one grace-hash partitioning pass: build and probe "
    "sides split into this many key-hash partitions, each joined "
    "independently (partition pairs are key-disjoint).  A partition "
    "whose build side still exceeds the window re-partitions "
    "recursively with a depth-salted hash, up to "
    "oocore.maxRecursionDepth.")
OOCORE_MAX_RECURSION = conf(
    "spark.rapids.memory.oocore.maxRecursionDepth", 4,
    "Bound on grace-hash re-partitioning recursion (and on external "
    "sort/aggregate re-spill rounds).  A partition that cannot be "
    "made to fit within this depth — pathological key skew, e.g. one "
    "key carrying the whole build side — fails with a descriptive "
    "error naming the skewed partition and the knobs, never a hang "
    "and never partial data.")
OOCORE_RUN_REPLICAS = conf(
    "spark.rapids.memory.oocore.runReplicas", 1,
    "Copies written per spilled run.  At 2+, a SpillCorruption on "
    "re-read (disk rot, faultInjection.spillCorruptRate) quarantines "
    "the corrupt buffer and recovers from a replica instead of "
    "failing the query (numSpillCorruptionsRecovered counts these); "
    "at 1 recovery needs a recompute closure or the corruption "
    "surfaces as the descriptive SpillCorruption error.  Replicas "
    "cost spill-tier capacity, not HBM.")

# --- query profiles (utils/profile.py) ---------------------------------------
PROFILE_ENABLED = conf(
    "spark.rapids.sql.profile.enabled", False,
    "Record a per-query observability profile, from accelerate() to the "
    "answer: a span tree (query -> plan:accelerate and its source "
    "uploads -> stage/exchange -> operator -> "
    "batch/compile/shuffle-fetch/retry -> readback) "
    "with thread-propagated parenting, dual-emitted to "
    "jax.profiler.TraceAnnotation (xprof captures still work) and to an "
    "in-process ring buffer, plus a structured event log (retries, "
    "fetch failures, blacklists, watchdog dumps, cancellations — all "
    "carrying the query id).  On collect() the spans, events, an "
    "EXPLAIN-with-metrics plan report, and a wall-clock breakdown "
    "(plan vs source upload vs compute vs pipeline wait vs shuffle vs "
    "compile vs retry-block) "
    "assemble into a QueryProfile kept in a bounded history.  Disabled "
    "(default) the batch hot loop allocates no tracer objects.")
PROFILE_HISTORY_SIZE = conf(
    "spark.rapids.sql.profile.historySize", 16,
    "How many completed QueryProfiles to retain in the in-process "
    "history (utils.profile.profile_history), queryable from tests and "
    "bench harnesses.  Oldest profiles are dropped first.")
PROFILE_EVENT_LOG_PATH = conf(
    "spark.rapids.sql.profile.eventLog.path", "",
    "When set, every profiled query appends its structured event "
    "records (span open/close, retries, fetch failures, blacklists, "
    "watchdog dumps, cancellations) to this file as JSON lines, each "
    "carrying the query id.  Empty disables the file sink; the "
    "in-process QueryProfile.events view is always available.")
PROFILE_CHROME_TRACE_PATH = conf(
    "spark.rapids.sql.profile.chromeTrace.path", "",
    "When set, every profiled query writes its span tree to this path "
    "as Chrome trace-event JSON (loadable in Perfetto / "
    "chrome://tracing).  A '{query_id}' placeholder in the path is "
    "substituted so consecutive queries do not overwrite each other.  "
    "Empty disables the file sink; QueryProfile.chrome_trace() always "
    "serves the same payload in-process.")
MOVEMENT_ENABLED = conf(
    "spark.rapids.sql.profile.movement.enabled", True,
    "When profiling is on, additionally record the per-query "
    "data-movement ledger (utils/movement.py): bytes + duration on "
    "every edge where data crosses a boundary — host->device uploads, "
    "device->host readbacks, spill tier migrations, shuffle wire "
    "bytes (compressed AND uncompressed), and ICI collective "
    "payloads.  The QueryProfile then carries a movement report "
    "(per-edge totals, effective GB/s vs roofline, compression "
    "ratios), Chrome-trace counter tracks, and data_movement event "
    "records.  Off: the profiler records time only, as before.")
MOVEMENT_ROOFLINE_GBPS = conf(
    "spark.rapids.sql.profile.movement.rooflineGBps", 0.0,
    "Bandwidth ceiling (GB/s) the movement report computes "
    "utilization against, for every edge.  0 (default) resolves the "
    "per-edge ceilings through the shared roofline table "
    "(spark.rapids.sql.profile.roofline.*, utils/roofline.py — the "
    "same source kernelprof judges kernels against); a non-zero "
    "value overrides ALL edges at once, e.g. with a number probed on "
    "the operator's own hardware, to judge every edge against "
    "measured hardware instead.")
MOVEMENT_MIN_EVENT_BYTES = conf(
    "spark.rapids.sql.profile.movement.minEventBytes", 65536,
    "Movement records at or above this many bytes also land in the "
    "structured event log as data_movement records (correlatable with "
    "retries, fetch failures, and watchdog dumps by query id); "
    "smaller records are aggregated into the ledger only, keeping the "
    "event ring for interesting transfers.  0 logs every record.")
RESIDENCY_ENABLED = conf(
    "spark.rapids.sql.profile.residency.enabled", True,
    "When profiling is on, additionally run the HBM residency ledger "
    "(utils/residency.py): every tracked device-resident allocation — "
    "tiered-store buffers (including shuffle catalog buffers), OOM-"
    "harness reservations, pinned SPMD gang inputs — registers "
    "per-buffer provenance (query id, operator site, size, tier) on "
    "creation and retires it on free/spill.  Profiled queries get a "
    "'-- residency --' section (HBM high-water mark, peak-instant "
    "composition by site/tier, leak verdict), Perfetto "
    "residency:<site> counter tracks, and an end-of-query leak check "
    "that dumps still-resident buffers with provenance; the "
    "slow-query log aggregates observed high-water marks per plan "
    "fingerprint (the feed learned admission budgets consume) and "
    "telemetry exports hbm_resident_bytes{tier} plus per-site "
    "gauges.  Tracking is process-sticky once the first residency-"
    "enabled query runs; off (default until then) every hook is one "
    "global read and allocates nothing.")
RESIDENCY_TIMELINE_SIZE = conf(
    "spark.rapids.sql.profile.residency.timelineSize", 4096,
    "Bound on per-query residency timeline samples (one per tracked "
    "alloc/free) backing the Perfetto residency:<site> counter "
    "tracks; oldest samples are dropped first.  The high-water mark "
    "and peak composition are exact regardless of this bound.")
RESIDENCY_LEAK_DUMP = conf(
    "spark.rapids.sql.profile.residency.leakDump", 8,
    "How many leaked buffers (still resident at query end) the "
    "residency report and event log render with full provenance "
    "(site, tier, kind, size, age); the leak COUNT is always exact.")
KERNELPROF_ENABLED = conf(
    "spark.rapids.sql.profile.kernels.enabled", False,
    "Per-kernel performance attribution (utils/kernelprof.py): every "
    "compiled executable in the KernelCache is wrapped so a sampled "
    "fraction of its dispatches is timed with a device sync "
    "(block_until_ready bracket, accounted via note_host_sync) and "
    "joined with XLA cost_analysis()/memory_analysis() — FLOPs, bytes "
    "accessed, temp allocation, captured once per kernel at its first "
    "dispatch (the actual compile point) — into achieved GFLOP/s and "
    "GB/s vs the conf-overridable roofline table "
    "(spark.rapids.sql.profile.roofline.*).  Profiled queries "
    "additionally get a '-- kernels --' section in their QueryProfile "
    "(top-N kernels by cumulative device time, roofline %, compile "
    "ms, dispatch counts, owning plan nodes) plus Perfetto kernel "
    "tracks, and the slow-query log names each fingerprint's hottest "
    "kernel.  Off (default): kernels dispatch raw — zero wrappers, "
    "zero syncs, bit-exact.")
KERNELPROF_SAMPLE_RATE = conf(
    "spark.rapids.sql.profile.kernels.sampleRate", 8,
    "Time every Nth dispatch of each kernel (1 = every dispatch).  "
    "Each timed dispatch pays one block_until_ready device sync, so "
    "the rate trades attribution accuracy (unsampled dispatches are "
    "estimated by scaling the sampled mean) against pipeline-overlap "
    "perturbation; 8 keeps measured overhead well inside the "
    "profiler's <2% budget while a rate of 1 makes the per-kernel "
    "device-time sum directly comparable to the wall-clock "
    "breakdown's compute category.")
KERNELPROF_COST_ANALYSIS = conf(
    "spark.rapids.sql.profile.kernels.costAnalysis", True,
    "Capture XLA cost_analysis()/memory_analysis() (FLOPs, bytes "
    "accessed, argument/output/temp sizes) once per kernel at its "
    "first dispatch, enabling the achieved-GFLOP/s / GB/s roofline "
    "join.  Capture re-lowers the jitted function once (a second "
    "trace+compile per kernel); disable to keep timing-only "
    "attribution on compile-dominated workloads.")
KERNELPROF_TOP_N = conf(
    "spark.rapids.sql.profile.kernels.topN", 12,
    "How many kernels (by cumulative attributed device time) the "
    "QueryProfile's '-- kernels --' section renders; the full "
    "per-fingerprint table stays queryable via "
    "QueryProfile.kernels and utils.kernelprof.catalog().")

# --- shared roofline table (utils/roofline.py) --------------------------------
# ONE conf-overridable source for every bandwidth/compute ceiling the
# instruments judge against: the movement ledger's per-edge GB/s
# utilization AND kernelprof's achieved-GFLOP/s / GB/s join both
# resolve through utils/roofline.py (two diverging nominal tables was
# the bug class this replaces).
ROOFLINE_UPLOAD_GBPS = conf(
    "spark.rapids.sql.profile.roofline.uploadGBps", 32.0,
    "Nominal host->device bandwidth ceiling (GB/s) for the movement "
    "report's upload edge (PCIe-gen4-x16-class host link).")
ROOFLINE_READBACK_GBPS = conf(
    "spark.rapids.sql.profile.roofline.readbackGBps", 32.0,
    "Nominal device->host bandwidth ceiling (GB/s) for the movement "
    "report's readback edge.")
ROOFLINE_SPILL_GBPS = conf(
    "spark.rapids.sql.profile.roofline.spillGBps", 32.0,
    "Nominal bandwidth ceiling (GB/s) for spill tier migrations "
    "(device->host->disk hops share the host-link ceiling).")
ROOFLINE_WIRE_GBPS = conf(
    "spark.rapids.sql.profile.roofline.wireGBps", 12.5,
    "Nominal shuffle-wire bandwidth ceiling (GB/s); the default "
    "models a 100 Gb/s DCN NIC.")
ROOFLINE_COLLECTIVE_GBPS = conf(
    "spark.rapids.sql.profile.roofline.collectiveGBps", 400.0,
    "Nominal ICI collective bandwidth ceiling (GB/s); the default is "
    "the v5e per-chip ICI nominal.")
ROOFLINE_HBM_GBPS = conf(
    "spark.rapids.sql.profile.roofline.hbmGBps", 0.0,
    "HBM bandwidth ceiling (GB/s) kernelprof judges per-kernel "
    "achieved GB/s (XLA bytes-accessed / device time) against.  0 "
    "(default) takes the nominal peak of the device kind JAX reports "
    "from utils/roofline.DEVICE_PEAKS (TPU v5 lite: 819, source "
    "Google Cloud 'TPU v5e'); on a device not in that table the "
    "roofline shares are None.  Set to a probed number to judge "
    "against measured hardware.")
ROOFLINE_PEAK_GFLOPS = conf(
    "spark.rapids.sql.profile.roofline.peakGflops", 0.0,
    "Compute ceiling (GFLOP/s) kernelprof judges per-kernel achieved "
    "GFLOP/s against.  0 (default) takes the device kind's nominal "
    "peak like hbmGBps (TPU v5 lite: 197 TFLOP/s in bf16); None on "
    "an unknown device.  A kernel's roofline utilization is the max of its "
    "compute fraction and its HBM-bandwidth fraction — whichever "
    "resource binds.")

PROFILE_EVENT_LOG_MAX_BYTES = conf(
    "spark.rapids.sql.profile.eventLog.maxBytes", 134217728,
    "Size-based rotation bound for the profile event-log JSONL sink "
    "(and the telemetry snapshot records riding it): when an append "
    "would push the file past this many bytes it is rotated to "
    "<path>.1 (older rotations shift to .2, .3, ...) so long-running "
    "serving never grows one unbounded file.  0 disables rotation "
    "(the pre-rotation behavior).")
PROFILE_EVENT_LOG_KEEP_FILES = conf(
    "spark.rapids.sql.profile.eventLog.keepFiles", 4,
    "How many rotated event-log files (<path>.1 .. <path>.N) to "
    "retain; the oldest is dropped at each rotation.  0 discards the "
    "full file at rotation instead of keeping any history.")

# --- engine-wide telemetry (utils/telemetry.py) -------------------------------
TELEMETRY_ENABLED = conf(
    "spark.rapids.sql.telemetry.enabled", False,
    "Run the process-wide telemetry layer: a live metrics registry "
    "(HBM budget/in-use and the admission ledger, TPU semaphore "
    "holds/waiters, scheduler queue depth and admission counters, "
    "kernel-cache size/evictions/compile time, prefetch hits/stalls, "
    "in-flight shuffle fetches, speculation/recovery counters, spill "
    "tier sizes, cumulative data-movement edge bytes) plus a low-rate "
    "background sampler that builds a device-utilization timeline — "
    "each sample attributed to busy-compute or a named idle cause "
    "(queue wait, semaphore wait, pipeline stall, host sync, compile, "
    "shuffle wait, truly idle).  Surfaced as a Prometheus text "
    "endpoint (telemetry.port), periodic JSONL snapshots on the "
    "profile event-log sink, and a slow-query log aggregated by plan "
    "fingerprint.  Disabled (default) every hook is a single "
    "module-global read and allocates nothing.")
TELEMETRY_PORT = conf(
    "spark.rapids.sql.telemetry.port", 0,
    "TCP port for the opt-in HTTP exporter (binds 127.0.0.1): GET "
    "/metrics serves Prometheus text exposition format, GET "
    "/telemetry a JSON snapshot (gauges + utilization summary + "
    "slow-query log).  0 (default) starts no server; the in-process "
    "views (utils.telemetry.prometheus_text / snapshot) are always "
    "available while telemetry is enabled.")
TELEMETRY_SAMPLE_PERIOD_MS = conf(
    "spark.rapids.sql.telemetry.samplePeriodMs", 100.0,
    "Period of the utilization sampler: each tick attributes the "
    "instant to busy-compute or a named idle cause using the "
    "already-instrumented heartbeats, semaphore, scheduler queue, "
    "prefetch queues, and in-flight fetches.  Low-rate by design — "
    "at the default 100ms a sample costs a handful of lock-free "
    "reads, far inside the telemetry overhead budget (<2%).")
TELEMETRY_TIMELINE_SIZE = conf(
    "spark.rapids.sql.telemetry.timelineSize", 4096,
    "Bound on retained utilization-timeline samples (a ring buffer; "
    "cause PERCENTAGES aggregate over the whole process lifetime "
    "regardless).  4096 samples at the default period is ~7 minutes "
    "of full-resolution timeline.")
TELEMETRY_SNAPSHOT_PERIOD_S = conf(
    "spark.rapids.sql.telemetry.snapshotPeriodS", 10.0,
    "Period of the JSONL telemetry snapshots (gauges + utilization "
    "summary) appended to the profile event-log sink "
    "(spark.rapids.sql.profile.eventLog.path) with kind="
    "'telemetry_snapshot'.  0 disables periodic snapshots; snapshots "
    "also require the event-log path to be set.")
TELEMETRY_SLOW_QUERY_LOG_SIZE = conf(
    "spark.rapids.sql.telemetry.slowQueryLog.size", 64,
    "How many distinct plan fingerprints the slow-query log retains "
    "(least-recently-updated dropped first).  Each entry aggregates "
    "the completed QueryProfiles of one plan shape: run count, "
    "p50/p95/max wall clock, and the top idle cause from the "
    "wall-clock breakdown.  Requires spark.rapids.sql.profile.enabled "
    "on the queries to be aggregated.")

# --- concurrent multi-query serving (exec/scheduler.py) ----------------------
SCHED_ENABLED = conf(
    "spark.rapids.sql.scheduler.enabled", True,
    "Admission-control concurrent queries against the accounted HBM "
    "budget: each top-level collect declares an HBM budget estimate "
    "(scheduler.queryBudgetBytes) and is admitted only while the sum "
    "of admitted budgets fits the device budget and fewer than "
    "scheduler.maxConcurrentQueries queries are in flight; otherwise "
    "it waits FIFO in a bounded queue and is shed with a descriptive "
    "TpuQueryRejected when the queue is full — queueing at the front "
    "door instead of thrashing the spill/retry lattice once the "
    "device is saturated.")
SCHED_MAX_CONCURRENT = conf(
    "spark.rapids.sql.scheduler.maxConcurrentQueries", 4,
    "Cap on concurrently ADMITTED queries per process (sessions, not "
    "tasks — spark.rapids.sql.concurrentGpuTasks still governs "
    "task-level device holds within each query).  Also the divisor "
    "for the default per-query budget when queryBudgetBytes is 0.")
SCHED_QUERY_BUDGET = conf(
    "spark.rapids.sql.scheduler.queryBudgetBytes", 0,
    "HBM bytes a query declares at admission (its working-set "
    "estimate, charged against the DeviceManager admission ledger "
    "for the query's lifetime).  0 derives an equal share: device "
    "budget / maxConcurrentQueries.  Declaring honestly matters in "
    "both directions: too low admits more queries than fit and "
    "pushes pressure into the OOM spill/retry lattice, too high "
    "queues queries the device could have served.")
SCHED_QUEUE_DEPTH = conf(
    "spark.rapids.sql.scheduler.queueDepth", 32,
    "Bound on queries waiting in the admission queue.  A query "
    "arriving at a full queue is rejected immediately with "
    "TpuQueryRejected (shed load early, keep latency bounded) rather "
    "than queued indefinitely.")
SCHED_QUEUE_TIMEOUT = conf(
    "spark.rapids.sql.scheduler.queueTimeout", 120.0,
    "Seconds a query may wait in the admission queue before being "
    "shed with TpuQueryRejected.  The queued wait is additionally "
    "registered as a task-class watchdog heartbeat that beats only "
    "as the queue drains, so a wedged queue produces a diagnostic "
    "dump naming every admitted query.")
RESULT_CACHE_ENABLED = conf(
    "spark.rapids.sql.scheduler.resultCache.enabled", False,
    "Cache collected query results keyed by (plan structural "
    "fingerprint, source-data identity, session-conf fingerprint) "
    "for repeated dashboard-style queries: a hit returns the cached "
    "result bit-exactly without touching the device.  Any conf "
    "change changes the key (stale-conf hits are impossible); plans "
    "with unrecognized leaves are simply not cached.  Off by "
    "default: in-memory sources are keyed by object identity, so "
    "callers that mutate source data in place must leave this off.")
RESULT_CACHE_MAX_BYTES = conf(
    "spark.rapids.sql.scheduler.resultCache.maxBytes", 268435456,
    "Byte bound on the result cache (LRU eviction; host memory).  A "
    "single result larger than this is never cached.")

# --- speculative partition execution (exec/speculation.py) -------------------
SPECULATION_ENABLED = conf(
    "spark.rapids.sql.speculation.enabled", False,
    "Launch duplicate attempts of straggling manager-lane map tasks "
    "(spark.rapids.shuffle.enabled with localExecutors >= 2): a task "
    "running far past its stage's completed-task median (a *slow* "
    "watchdog classification, distinct from *hung*) is re-executed "
    "from the exchange's retained lineage on another in-process "
    "executor; whichever attempt commits its map output first wins "
    "and the loser is cancelled via its per-attempt CancelToken.  "
    "First-wins commit is epoch-guarded in the MapOutputRegistry, so "
    "a losing attempt can never publish — results stay bit-exact.  "
    "The p95/p99 lever for one degraded executor; speculation never "
    "fires on a healthy stage.")
SPECULATION_MULTIPLIER = conf(
    "spark.rapids.sql.speculation.multiplier", 3.0,
    "How many times slower than the stage's completed-task median a "
    "running task must be before a speculative duplicate launches "
    "(spark.speculation.multiplier analog).")
SPECULATION_MIN_RUNTIME_MS = conf(
    "spark.rapids.sql.speculation.minTaskRuntimeMs", 100.0,
    "A task is never speculated before running at least this long — "
    "guards against duplicating every task of a stage whose median is "
    "microseconds.")
SPECULATION_MIN_COMPLETED = conf(
    "spark.rapids.sql.speculation.minCompletedTasks", 2,
    "Completed tasks the stage needs before its median is trusted for "
    "slow classification (spark.speculation.quantile analog: no "
    "speculation while the baseline is unknown).")

# --- whole-stage fusion (plan/fusion.py) -------------------------------------
FUSION_ENABLED = conf(
    "spark.rapids.sql.fusion.enabled", True,
    "Collapse fusible operator chains between pipeline breaks "
    "(project->filter->project, and project/filter chains feeding a "
    "partial or complete aggregation's update lane) into ONE jitted "
    "XLA program per stage: the per-operator expression evaluators "
    "compose into a single kernel, so intermediate ColumnarBatch "
    "materialization and per-operator dispatch disappear from the hot "
    "path.  The composed expression DAG is simplified "
    "(cross-operator constant folding + common-subexpression dedup) "
    "before compiling, and compiled programs land in the shared "
    "KernelCache keyed by the fused-stage structural signature.  A "
    "stage containing an expression the fuser cannot compose (e.g. "
    "ANSI-checked casts) deopts to the unfused per-operator lane — "
    "only that stage, never the query.")
SPMD_ENABLED = conf(
    "spark.rapids.sql.spmd.enabled", False,
    "Execute fused stages as ONE sharded XLA program over the active "
    "device mesh (exec/spmd.py): the stage's partition batches are "
    "stacked along a leading axis laid out with NamedSharding(mesh, "
    "P('data')), padded per shard with explicit row-count masks so "
    "ragged partitions stay bit-exact, and the whole "
    "project->filter chain runs in one jit-with-shardings dispatch — "
    "one Python dispatch per stage instead of one per partition, with "
    "XLA owning the (few) cross-shard collectives.  Requires an "
    "active mesh (spark_rapids_tpu.parallel.mesh.set_active_mesh); "
    "without one, or on unsupported stages, uneven batch layouts, or "
    "trace failure, the stage deopts to the per-partition lane "
    "(numSpmdDeopts).  Also changes plan shape: fusible chains stay "
    "standalone FusedStageExec nodes (single-operator chains "
    "included) instead of folding into the aggregate update lane, so "
    "the SPMD program sees them.  Off (default): byte-identical to "
    "the per-partition engine.")
KERNEL_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.sql.kernelCache.maxEntries", 512,
    "Entry-count bound on the process-global compiled-kernel LRU "
    "(exec/base.py KernelCache).  Fused-stage keys multiply cache "
    "pressure (every stage shape x batch signature is an entry), so "
    "the cache evicts least-recently-used executables past this "
    "bound; the eviction count is surfaced in the bench summary "
    "(kernel_cache_evictions).  XLA CPU clients have been observed "
    "to segfault with thousands of live loaded executables — raise "
    "with care.")

# --- async pipelined execution (exec/pipeline.py) ----------------------------
# env-overridable defaults so CI lanes (scripts/run_suite.sh pipeline)
# can flip the whole suite without threading a conf through every test
import os as _os

PIPELINE_ENABLED = conf(
    "spark.rapids.sql.pipeline.enabled",
    _bool(_os.environ.get("SPARK_RAPIDS_TPU_PIPELINE", "true")),
    "Overlap pipeline stages with bounded background prefetch: at "
    "pipeline breaks (scan->compute, both sides of a shuffle exchange, "
    "coalesce boundaries, AQE stage materialization) a producer thread "
    "runs the upstream iterator prefetchDepth batches ahead while the "
    "consumer computes, so host orchestration, H2D transfer, and device "
    "kernels overlap instead of strictly alternating.  Producers obey "
    "the TPU semaphore discipline: one blocked on a full queue never "
    "holds the semaphore.")
PIPELINE_PREFETCH_DEPTH = conf(
    "spark.rapids.sql.pipeline.prefetchDepth",
    int(_os.environ.get("SPARK_RAPIDS_TPU_PIPELINE_DEPTH", "2")),
    "How many batches a pipeline producer may run ahead of its "
    "consumer at each pipeline break (the prefetch queue bound).  "
    "Bounds peak device memory at ~depth extra batches per break; 0 "
    "disables prefetch at that break like pipeline.enabled=false.")

# --- I/O formats (reference RapidsConf.scala format enables + Spark's
# spark.sql.files.* split planning keys) --------------------------------------
PARQUET_ENABLED = conf("spark.rapids.sql.format.parquet.enabled", True,
                       "Enable parquet scan/write acceleration.")
PARQUET_READ_ENABLED = conf("spark.rapids.sql.format.parquet.read.enabled",
                            True, "Enable accelerated parquet reads.")
PARQUET_WRITE_ENABLED = conf("spark.rapids.sql.format.parquet.write.enabled",
                             True, "Enable accelerated parquet writes.")
ORC_ENABLED = conf("spark.rapids.sql.format.orc.enabled", True,
                   "Enable ORC scan/write acceleration.")
ORC_READ_ENABLED = conf("spark.rapids.sql.format.orc.read.enabled", True,
                        "Enable accelerated ORC reads.")
ORC_WRITE_ENABLED = conf("spark.rapids.sql.format.orc.write.enabled", True,
                         "Enable accelerated ORC writes.")
CSV_ENABLED = conf("spark.rapids.sql.format.csv.enabled", True,
                   "Enable CSV scan acceleration (reads only).")
CSV_READ_ENABLED = conf("spark.rapids.sql.format.csv.read.enabled", True,
                        "Enable accelerated CSV reads.")
MULTITHREAD_READ_NUM_THREADS = conf(
    "spark.rapids.sql.format.parquet.multiThreadedRead.numThreads", 20,
    "Host file-buffering threads per executor (small-file optimization).")
MAX_PARTITION_BYTES = conf("spark.sql.files.maxPartitionBytes", 134217728,
                           "Max bytes packed into one scan partition.")
FILE_OPEN_COST = conf("spark.sql.files.openCostInBytes", 4194304,
                      "Estimated cost in bytes of opening a file when "
                      "packing splits into scan partitions.")
MIN_PARTITION_NUM = conf("spark.sql.files.minPartitionNum", 8,
                         "Suggested minimum scan partition count (Spark "
                         "defaults this to the cluster parallelism).")

# --- shuffle (reference :592-631) -------------------------------------------
RAPIDS_SHUFFLE_ENABLED = conf(
    "spark.rapids.shuffle.enabled", False,
    "Route exchanges through the accelerated shuffle manager (spillable "
    "catalog + ICI/DCN transport) instead of the in-process exchange.")
SHUFFLE_TRANSPORT_CLASS = conf(
    "spark.rapids.shuffle.transport.class",
    "spark_rapids_tpu.shuffle.ici_transport.IciShuffleTransport",
    "Fully-qualified RapidsShuffleTransport implementation.")
SHUFFLE_MAX_RECV_INFLIGHT = conf(
    "spark.rapids.shuffle.maxMetadataFetchSize", 1073741824,
    "Max in-flight receive bytes per client (throttle).")
SHUFFLE_BOUNCE_BUFFER_SIZE = conf(
    "spark.rapids.shuffle.bounceBuffers.size", 4194304,
    "Bounce/staging buffer size for cross-slice (DCN) transfers.")
SHUFFLE_BOUNCE_BUFFER_COUNT = conf(
    "spark.rapids.shuffle.bounceBuffers.count", 32,
    "Number of staging buffers per transport direction.")
SHUFFLE_COMPRESSION_CODEC = conf(
    "spark.rapids.shuffle.compression.codec", "none",
    "Codec for serialized shuffle payloads on the transport wire: "
    "none, copy (testing), lz4, zstd.")
SHUFFLE_FAULT_DROP_RATE = conf(
    "spark.rapids.shuffle.transport.faultInjection.dropRate", 0.0,
    "TEST ONLY: probability that the transport server aborts a "
    "transfer mid-stream (connection-loss injection; the reference "
    "builds UCX with --enable-fault-injection for the same class of "
    "soak testing). The client's bounded-retry path must recover.",
    internal=True)
SHUFFLE_FAULT_CORRUPT_RATE = conf(
    "spark.rapids.shuffle.transport.faultInjection.corruptRate", 0.0,
    "TEST ONLY: probability that a DATA chunk payload is corrupted on "
    "the wire; the receiver's deserialization/CRC checks must detect "
    "it and the fetch must retry.", internal=True)
SHUFFLE_FAULT_SEED = conf(
    "spark.rapids.shuffle.transport.faultInjection.seed", 0,
    "Deterministic seed for fault injection.", internal=True)
SHUFFLE_FAULT_PEER_KILL_FRAMES = conf(
    "spark.rapids.shuffle.transport.faultInjection.peerKillAfterFrames", 0,
    "TEST ONLY: after serving this many DATA frames (across both the "
    "TCP and loopback lanes) the transport kills its own peer: sockets "
    "close mid-stream, the accept loop stops, and the loopback "
    "registration disappears — a hard executor loss, not a polite "
    "error.  The shuffle fault-recovery subsystem must invalidate the "
    "peer's map outputs and recompute them.  0 disables.",
    internal=True)
SHUFFLE_FETCH_MAX_RETRIES = conf(
    "spark.rapids.shuffle.fetch.maxRetries", 3,
    "Transfer-level retry budget per peer fetch: a failed transaction "
    "(mid-stream abort, wire corruption, dead socket) is retried on a "
    "fresh connection up to this many times before the fetch surfaces "
    "a FetchFailedError to the stage-recovery layer (reference "
    "RapidsShuffleClient FetchRetry).")
SHUFFLE_FETCH_BACKOFF_BASE_MS = conf(
    "spark.rapids.shuffle.fetch.backoff.baseMs", 50.0,
    "Base delay for exponential backoff between fetch retries: attempt "
    "k sleeps min(capMs, baseMs * 2^(k-1)) with +/-50% deterministic "
    "jitter (seeded from faultInjection.seed when set), so a flapping "
    "peer is not hammered with immediate reconnects.")
SHUFFLE_FETCH_BACKOFF_CAP_MS = conf(
    "spark.rapids.shuffle.fetch.backoff.capMs", 2000.0,
    "Upper bound on a single fetch-retry backoff sleep.")
SHUFFLE_RECOVERY_ENABLED = conf(
    "spark.rapids.shuffle.recovery.enabled", True,
    "Recover from shuffle fetch failures instead of failing the query: "
    "a FetchFailedError at the reduce side invalidates the failed "
    "peer's map outputs (per-shuffle epoch bump), recomputes only the "
    "lost map tasks from the exchange's retained lineage, and retries "
    "the reduce — the role Spark's DAG scheduler plays for the "
    "reference's FetchFailedException.")
SHUFFLE_RECOVERY_MAX_STAGE_ATTEMPTS = conf(
    "spark.rapids.shuffle.recovery.maxStageAttempts", 4,
    "Bounded stage retries: how many times a reduce partition may be "
    "attempted (initial try + recoveries) before the query fails with "
    "a descriptive FetchFailedError — never a hang, never a partial "
    "result (Spark's spark.stage.maxConsecutiveAttempts analog).")
SHUFFLE_BLACKLIST_THRESHOLD = conf(
    "spark.rapids.shuffle.recovery.blacklist.failureThreshold", 3,
    "Consecutive recovery-attributed failures after which a peer "
    "address is blacklisted: readers route around it via the "
    "MapStatus's alternate address and map tasks stop being placed on "
    "it, instead of waiting out its full timeout every stage.")
SHUFFLE_BLACKLIST_DECAY_S = conf(
    "spark.rapids.shuffle.recovery.blacklist.decaySeconds", 30.0,
    "A blacklist entry expires after this long and the peer gets a "
    "fresh consecutive-failure budget — a recovered (flapping) "
    "executor rejoins service instead of being shunned forever.")
SHUFFLE_LOCAL_EXECUTORS = conf(
    "spark.rapids.shuffle.localExecutors", 1,
    "Number of in-process executor environments the manager-lane "
    "exchange spreads map tasks across (round-robin).  >1 makes map "
    "outputs genuinely remote to the reducing executor — loopback/TCP "
    "fetches, fault injection, and recovery all exercise multi-executor "
    "behavior in one process, like the reference's mocked-transport "
    "suites.  1 (default) keeps the single local manager.")
SHUFFLE_REPLICATION_FACTOR = conf(
    "spark.rapids.shuffle.replication.factor", 1,
    "Copies of each map output across in-process executors (1 = "
    "primary only, the default).  At 2+ the CachingShuffleWriter "
    "pushes each partition's serialized payload to factor-1 backup "
    "executors at write time: hedged fetches "
    "(spark.rapids.shuffle.hedge.enabled) can race a replica against "
    "a slow primary, and shuffle recovery promotes a live replica to "
    "primary on peer loss instead of recomputing from lineage "
    "(recompute remains the fallback when no replica survives).  "
    "Costs one extra serialization + host-store copy per replicated "
    "partition (replicatedBytes on the exchange's metrics and the "
    "movement ledger's wire:replicate site).")
SHUFFLE_HEDGE_ENABLED = conf(
    "spark.rapids.shuffle.hedge.enabled", False,
    "Hedge slow shuffle fetches: when a remote fetch has not "
    "completed after the hedge delay (hedge.delayMs floor, or the "
    "hedge.quantile of recently observed fetch durations once enough "
    "samples exist), issue the same block request to a replica peer "
    "(shuffle.replication.factor >= 2) and keep the first complete, "
    "uncorrupted response — the loser is cancelled and its buffers "
    "freed, its wire bytes charged to the ledger's wire:wasted site.  "
    "First-wins is bit-exact: both attempts serve identical "
    "serialized payloads.")
SHUFFLE_HEDGE_DELAY_MS = conf(
    "spark.rapids.shuffle.hedge.delayMs", 1000.0,
    "Floor (and cold-start fallback) for the hedge trigger delay: a "
    "fetch outstanding this long fires the hedge even before enough "
    "latency samples exist to compute the quantile.")
SHUFFLE_HEDGE_QUANTILE = conf(
    "spark.rapids.shuffle.hedge.quantile", 0.95,
    "Latency quantile of recently completed fetches above which an "
    "outstanding fetch is considered straggling and hedged (once >= 8 "
    "samples exist; the effective delay is max(quantile latency, "
    "hedge.delayMs)).")
MESH_EXCHANGE_ENABLED = conf(
    "spark.rapids.shuffle.meshExchange.enabled", True,
    "Route hash shuffle exchanges through the device-mesh ICI all-to-all "
    "collective when an active mesh is set "
    "(spark_rapids_tpu.parallel.mesh.set_active_mesh) and the exchange "
    "is mesh-routable (hash keys are plain columns, partition count == "
    "mesh size). The TCP/manager lane remains the DCN fallback — the "
    "reference's equivalent split is UCX-inside-the-shuffle-manager "
    "(RapidsShuffleInternalManager.scala:199, UCXShuffleTransport.scala:47).")

# --- python / udf -----------------------------------------------------------
PYTHON_CONCURRENT_WORKERS = conf(
    "spark.rapids.python.concurrentPythonWorkers", 0,
    "Cap on concurrent accelerated python UDF workers (0 = unlimited).")
PYTHON_DAEMON_ENABLED = conf(
    "spark.rapids.python.daemon.enabled", False,
    "Run vectorized python UDFs in out-of-process daemon workers "
    "(Arrow IPC over pipes) instead of in-process — process isolation "
    "at one host round-trip of cost (reference python/rapids/daemon.py).")
PYTHON_ON_TPU = conf(
    "spark.rapids.python.onTpu.enabled", False,
    "Allow daemon UDF workers to initialize the TPU platform; off by "
    "default because the chip is single-process and belongs to the "
    "executor (reference RAPIDS_PYTHON_ENABLED gate, "
    "python/rapids/worker.py:22-30).")
PYTHON_MEM_LIMIT = conf(
    "spark.rapids.python.memory.limitBytes", 0,
    "Address-space rlimit per daemon UDF worker, 0 = unlimited (the "
    "role of the reference's per-worker RMM pool size, "
    "python/rapids/worker.py:34-50).")
UDF_COMPILER_ENABLED = conf("spark.rapids.sql.udfCompiler.enabled", True,
                            "Compile Python UDF bytecode to expressions.")

PALLAS_Q1_ENABLED = conf(
    "spark.rapids.tpu.pallas.q1.enabled", False,
    "Use the Pallas kernel for SINGLE-batch TPC-H Q1 dispatches. In "
    "this dispatch-overhead-bound mode the lighter XLA einsum kernel "
    "stays the single-batch default (relative speed not measured on "
    "the current machine); see q1Fused for the stacked mode.")
DICT_GROUPBY_ENABLED = conf(
    "spark.rapids.tpu.dictGroupby.enabled", True,
    "Planner-automatic sort-free grouped aggregation via the fused "
    "Pallas one-hot kernel when a single integral group key's runtime "
    "range fits dictGroupby.maxGroups (Sum/Count/Average over FLOAT32 "
    "or integral inputs, Count over anything). The whole batch runs as "
    "ONE dispatch (window slots + grouped sum + finalize); a "
    "first-batch probe sizes the dictionary and per-batch overflow "
    "counts trigger fallback to the sort path. The switch chooses a "
    "lane and never a precision: the kernel accumulates in f32, so a "
    "Sum/Average of a FLOAT64 input never takes it (it sums in "
    "float64 on the sort-segment lane whatever this says); FLOAT32 "
    "Sum/Average additionally require variableFloatAgg.enabled (the "
    "order of the additions varies); integral measures are "
    "exact-or-deopt; Count-only plans are exact.")
DICT_GROUPBY_MAX_GROUPS = conf(
    "spark.rapids.tpu.dictGroupby.maxGroups", 32768,
    "Max runtime key range for the dictionary group-by fast path. The "
    "one-hot kernel tiles its VMEM block by group count, so cost grows "
    "mildly with range (measured: 4K groups 100ms, 16K 118ms, 64K "
    "332ms at 2M rows); 32K covers e.g. TPCx-BB q27's ~26K items "
    "while staying ~2x the 4K floor.")
BANDED_GROUPBY_ENABLED = conf(
    "spark.rapids.tpu.bandedGroupby.enabled", True,
    "Sum/Count/Average group-bys aggregate through the banded windowed "
    "MXU kernel (ops/grouped_window.py) after the grouping sort: "
    "per-block one-hot local tables merged by one small matmul, no "
    "serialized scatters, no positions/segmented-scan machinery — and "
    "group count is UNBOUNDED (no dictGroupby range budget). "
    "The switch chooses a lane and never a precision: accumulation is "
    "f32, so a Sum/Average whose input (update phase) or intermediate "
    "(merge phase; an Average's always) is FLOAT64 never takes the "
    "lane and sums in float64 on the sort-segment lane whatever this "
    "says; integral measures are exact-or-deopt via the sum(|v|) "
    "certificate, FLOAT32 measures additionally require "
    "variableFloatAgg.enabled (the order of the additions varies). "
    "Group keys of any sortable type are recovered through "
    "first-row-index limb measures + one gather.")
HASH_GROUPING_ENABLED = conf(
    "spark.rapids.tpu.hashGrouping.enabled", True,
    "Wide grouping key sets (aggregate GROUP BY, window PARTITION BY) "
    "sort by two murmur3-derived words instead of the lexicographic "
    "key encode, whose width scales with key content (string keys "
    "emit one 9-bit sort word slice PER CHARACTER; a 15-column string "
    "grouper is ~100 packed words and its XLA compile alone runs "
    "minutes). Exact: segment boundaries come from the actual "
    "adjacent key values, and a detected 64-bit hash collision deopts "
    "the query to the lexicographic lane via the deferred-check "
    "retry.")
DENSE_JOIN_ENABLED = conf(
    "spark.rapids.tpu.denseJoin.enabled", True,
    "Direct-address equi-join fast path: when a single integral build "
    "key's runtime span fits denseJoin.maxSpan and the keys are unique "
    "(PK-FK joins on dense surrogate keys), the build side becomes a "
    "dense slot table and each probe batch is ONE dispatch of two fused "
    "gathers — no concat, no sort.  Falls back to the sort-merge kernel "
    "otherwise.")
DENSE_JOIN_MAX_SPAN = conf(
    "spark.rapids.tpu.denseJoin.maxSpan", 1 << 22,
    "Max build-key span for the direct-address join table (table memory "
    "is 8 bytes per slot).")
PALLAS_Q1_FUSED_ENABLED = conf(
    "spark.rapids.tpu.pallas.q1Fused.enabled", True,
    "Use the Pallas single-HBM-pass kernel for STACKED multi-batch Q1 "
    "dispatches (the device-side batch loop). Measured 3.0x the XLA "
    "einsum formulation on v5e (~2060 vs 689 Mrows/s over 8x16.8M "
    "rows): XLA materializes the one-hot einsum operands in HBM (~19GB "
    "traffic for 3.8GB of input) where the Pallas kernel touches each "
    "input byte once (ops/pallas_kernels.py).")

# --- adaptive query execution ----------------------------------------------
# Spark-owned keys the plugin reads (reference: AQE is driven by Spark's
# spark.sql.adaptive.* confs; the plugin supplies GpuCustomShuffleReaderExec
# and the query-stage prep rule, GpuOverrides.scala:1807-1881).
ADAPTIVE_ENABLED = conf(
    "spark.sql.adaptive.enabled", False,
    "Re-plan at query-stage boundaries from runtime shuffle statistics.")
COALESCE_PARTITIONS_ENABLED = conf(
    "spark.sql.adaptive.coalescePartitions.enabled", True,
    "Merge adjacent small reduce partitions after a shuffle stage.")
ADVISORY_PARTITION_SIZE = conf(
    "spark.sql.adaptive.advisoryPartitionSizeInBytes", 64 * 1024 * 1024,
    "Target post-shuffle partition size for AQE partition coalescing.")
AUTO_BROADCAST_THRESHOLD = conf(
    "spark.sql.autoBroadcastJoinThreshold", 10 * 1024 * 1024,
    "Max build-side bytes for the AQE shuffled-hash-join to "
    "broadcast-join demotion (-1 disables).")
BROADCAST_TIMEOUT = conf(
    "spark.sql.broadcastTimeout", 300,
    "Seconds allowed for materializing a broadcast build side before "
    "the exchange fails (reference GpuBroadcastExchangeExec timeout "
    "on the build-side collect future).")
MAX_BROADCAST_TABLE_BYTES = conf(
    "spark.rapids.tpu.maxBroadcastTableBytes", 8 << 30,
    "Hard cap on a broadcast build side's device bytes; exceeding it "
    "fails the query with a clear error instead of exhausting HBM "
    "(Spark's 8GB broadcast-table limit).")


def op_enable_key(kind: str, name: str) -> str:
    """Auto-derived per-operator enable key
    (reference GpuOverrides.scala:129-137)."""
    return f"spark.rapids.sql.{kind}.{name}"


class RapidsConf:
    """Immutable snapshot of config values, read once at plan time
    (reference reads per-query: GpuOverrides.scala:1885)."""

    def __init__(self, settings: Optional[dict[str, Any]] = None):
        self._settings = dict(settings or {})

    def is_set(self, key: str) -> bool:
        """True when `key` was EXPLICITLY set on this conf (as opposed
        to resolving through the registry default) — lets layered
        defaults (e.g. the test harness's conservative global watchdog
        deadlines) yield to per-session settings without shadowing
        them."""
        return key in self._settings

    def get(self, key: str, default: Any = None) -> Any:
        if key in self._settings:
            val = self._settings[key]
            entry = _REGISTRY.get(key)
            if entry is not None and isinstance(val, str):
                return entry.converter(val)
            return val
        entry = _REGISTRY.get(key)
        if entry is not None:
            return entry.default
        return default

    def __getitem__(self, entry: ConfEntry) -> Any:
        return self.get(entry.key, entry.default)

    def is_op_enabled(self, kind: str, name: str, default: bool = True) -> bool:
        return _bool(self.get(op_enable_key(kind, name), default))

    def with_overrides(self, **kv) -> "RapidsConf":
        s = dict(self._settings)
        s.update({k.replace("__", "."): v for k, v in kv.items()})
        return RapidsConf(s)

    def set(self, key: str, value: Any) -> "RapidsConf":
        s = dict(self._settings)
        s[key] = value
        return RapidsConf(s)

    def fingerprint(self) -> tuple:
        """Stable hashable identity of every EXPLICIT setting — the
        result cache's conf component, so two sessions differing in any
        setting can never serve each other's cached results."""
        return tuple(sorted((k, repr(v))
                            for k, v in self._settings.items()))

    @property
    def sql_enabled(self) -> bool:
        return self[SQL_ENABLED]


_active = threading.local()


def get_active_conf() -> RapidsConf:
    c = getattr(_active, "conf", None)
    if c is None:
        # execution-time fallback: a helper thread carrying a query
        # context (TaskContext.query_ctx / scheduler-scoped) reads ITS
        # query's conf snapshot, never another session's thread-local
        # or the registry defaults — the PR 2 captured-default-conf
        # bug class, closed at the resolver
        try:
            from spark_rapids_tpu.exec import scheduler as _S
            qc = _S.current()
            if qc is not None:
                return qc.conf
        except ImportError:
            pass
        c = RapidsConf()
        _active.conf = c
    return c


def set_active_conf(conf_: RapidsConf) -> None:
    _active.conf = conf_


@contextmanager
def session(conf_: Optional[RapidsConf]):
    """Install `conf_` as the active conf for the duration (the
    driver-side analog of Spark's session-scoped SQLConf: plan-time conf
    decisions and run-time conf reads see the same values —
    GpuOverrides.scala:1885 reads conf at plan time; our collect()
    installs the plan's conf for execution)."""
    if conf_ is None:
        yield
        return
    prev = getattr(_active, "conf", None)
    _active.conf = conf_
    try:
        yield
    finally:
        _active.conf = prev


def help_text() -> str:
    """Generate docs/configs.md content (reference ConfHelper.makeConfAnchor,
    RapidsConf.scala help())."""
    lines = ["# Configuration", "",
             "| Key | Default | Description |", "|---|---|---|"]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.internal:
            continue
        lines.append(f"| `{e.key}` | {e.default} | {e.doc} |")
    return "\n".join(lines) + "\n"


def write_docs(path: str = "docs/configs.md") -> None:
    import os
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(help_text())
