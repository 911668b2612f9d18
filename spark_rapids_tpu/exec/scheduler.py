"""Concurrent multi-query serving: query contexts, admission control,
and the plan-fingerprint result cache.

PRs 1-5 made a single query survive OOM, peer death, and hangs — but the
engine still executed one `collect()` at a time while HBM sat 1-3%
utilized.  This module is the serving layer in front of
`TpuExec.collect` that lets tens of concurrent sessions share one
accelerator the way the Presto-on-GPU work shares a GPU between
interactive tenants, with the resource-accounting discipline Theseus
argues decides whether an accelerator engine stays healthy under load:

* **QueryContext** — one per top-level query: the query id, the conf
  SNAPSHOT (no globals resolved mid-query), the `CancelToken`, the
  per-query watchdog stats, the per-query deferred-check registry, the
  per-query profile tracer, and the execution epoch for
  `CommonSubplanExec` caches.  Carried thread-locally on the driver
  thread and threaded through `TaskContext.query_ctx` to every helper
  thread (pipeline producers, AQE fills, shuffle fetch threads), so a
  fault injected into query A — OOM, peer kill, hang — cancels,
  retries, or fails A alone and never bleeds into query B.
* **QueryScheduler** — admission control against the `DeviceManager`
  HBM admission ledger: a query declares an HBM budget estimate
  (`spark.rapids.sql.scheduler.queryBudgetBytes`, defaulting to an
  equal share of the accounted arena) and is admitted only while the
  sum of admitted budgets fits the device budget AND fewer than
  `maxConcurrentQueries` queries are in flight.  Otherwise it queues
  FIFO (bounded by `queueDepth`, watched by a task-class heartbeat so
  a wedged queue is watchdog-visible) and sheds load with a
  descriptive `TpuQueryRejected` when the queue is full or the
  `queueTimeout` passes — queueing at the front door instead of
  thrashing the spill/retry lattice once saturated.
* **ResultCache** — a byte-bounded LRU keyed by (plan structural
  fingerprint, source-data identity, session-conf fingerprint) for
  repeated dashboard-style queries: a hit returns the cached pandas
  result (copied, bit-exact) without touching the device; any conf
  change changes the key, so stale-conf hits are impossible.  Plans
  with leaves the fingerprinter does not recognize are simply not
  cached — never a wrong answer.

The collect-side handshake is `CollectScope` (used by
`TpuExec.collect`): the outermost collect on a thread with no live
QueryContext creates one, begins its profile, admits it, and serializes
on the PLAN INSTANCE lock (two sessions sharing one plan object would
race its CommonSubplanExec caches and metrics; distinct plan instances
— the normal case — run fully concurrently).
"""
from __future__ import annotations

import collections
import itertools
import logging
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

from spark_rapids_tpu import config as C

log = logging.getLogger("spark_rapids_tpu.scheduler")


class TpuQueryRejected(RuntimeError):
    """Admission control shed this query: the device is saturated and
    the wait queue is full (or the queue deadline passed).  Carries a
    snapshot of the admission state so the caller can size budgets."""


# ---------------------------------------------------------------------------
# execution epochs: minted process-globally so no two query attempts can
# ever collide on a CommonSubplanExec cache tag, scoped per-query so
# concurrent queries' epochs don't invalidate each other's caches
_EPOCH_COUNTER = itertools.count(1)
_EPOCH_LOCK = threading.Lock()
_LAST_EPOCH = 0

_QUERY_IDS = itertools.count(1)


def new_epoch() -> int:
    global _LAST_EPOCH
    with _EPOCH_LOCK:
        _LAST_EPOCH = next(_EPOCH_COUNTER)
        return _LAST_EPOCH


def current_epoch() -> int:
    """The epoch `CommonSubplanExec` caches are scoped to: the current
    query's attempt epoch, or (no query in flight — direct
    execute_partitions in tests) the last minted value."""
    qc = current()
    if qc is not None and qc.epoch:
        return qc.epoch
    return _LAST_EPOCH


# ---------------------------------------------------------------------------
class QueryContext:
    """Everything one in-flight query owns.  Created by the outermost
    collect (via CollectScope), installed thread-locally on the driver
    thread, and propagated to helper threads through
    `TaskContext.query_ctx` / `scoped()`."""

    __slots__ = ("query_id", "conf", "token", "stats", "pending_checks",
                 "tracer", "epoch", "budget_bytes", "admitted",
                 "owner_thread", "created", "report_plan", "_depth",
                 "_lock")

    def __init__(self, conf: Optional[C.RapidsConf] = None):
        from spark_rapids_tpu.utils import watchdog as W
        self.query_id = f"q{next(_QUERY_IDS):06d}-{os.getpid() & 0xffff}"
        self.conf = conf if conf is not None else C.get_active_conf()
        self.token = W.CancelToken()
        #: per-query watchdog counters (timeouts/cancels/dumps/slowest
        #: heartbeat) — query A's trip must never charge query B's plan
        self.stats = {"timeouts": 0, "cancels": 0, "dumps": 0,
                      "slowest_heartbeat_ms": 0}
        #: per-query deferred-check registry (utils/checks.py): checks
        #: from concurrent queries must not interleave in one list
        self.pending_checks: list = []
        self.tracer = None           # utils/profile.QueryTracer or None
        self.epoch = 0               # minted per top-level attempt
        self.budget_bytes = 0        # declared HBM admission budget
        self.admitted = False        # holds an admission-ledger slot
        self.owner_thread = threading.get_ident()
        self.created = time.monotonic()
        self.report_plan = None      # outermost plan, for the profile
        self._depth = 0              # collect() nesting within this query
        self._lock = threading.Lock()

    def enter_collect(self) -> bool:
        with self._lock:
            self._depth += 1
            return self._depth == 1

    def exit_collect(self) -> bool:
        with self._lock:
            self._depth -= 1
            return self._depth == 0

    @property
    def collect_depth(self) -> int:
        with self._lock:
            return self._depth


_TLS = threading.local()


def current() -> Optional[QueryContext]:
    """The calling thread's QueryContext: the thread-locally installed
    one (driver thread / `scoped` helper threads), else the one riding
    the thread's TaskContext (pipeline producers)."""
    qc = getattr(_TLS, "qc", None)
    if qc is not None:
        return qc
    from spark_rapids_tpu.memory.semaphore import TaskContext
    ctx = TaskContext.get()
    if ctx is not None:
        return getattr(ctx, "query_ctx", None)
    return None


@contextmanager
def scoped(qc: Optional[QueryContext]):
    """Install `qc` as this thread's QueryContext for the duration —
    helper threads (AQE fills, shuffle fetch threads, pipeline
    producers) capture their creator's context via `current()` and
    enter this, so cancellation, conf reads, deferred checks, and
    profile events all resolve to the right query.  None is a no-op."""
    if qc is None:
        yield None
        return
    prev = getattr(_TLS, "qc", None)
    _TLS.qc = qc
    try:
        yield qc
    finally:
        _TLS.qc = prev


# ---------------------------------------------------------------------------
class _QueueEntry:
    __slots__ = ("qc", "budget", "max_queries", "event", "enqueued",
                 "admitted", "rejected")

    def __init__(self, qc: QueryContext, budget: int, max_queries: int):
        self.qc = qc
        self.budget = budget
        self.max_queries = max_queries
        self.event = threading.Event()
        self.enqueued = time.monotonic()
        self.admitted = False
        self.rejected: Optional[str] = None


class QueryScheduler:
    """Process singleton gatekeeper in front of query execution."""

    _instance: Optional["QueryScheduler"] = None
    _ilock = threading.Lock()

    def __init__(self):
        self._cv = threading.Condition()
        self._queue: list[_QueueEntry] = []   # FIFO
        self._stats = {"admitted": 0, "queued": 0, "rejected": 0,
                       "queue_timeouts": 0, "max_queue_depth": 0,
                       "longest_queue_wait_ms": 0}

    @classmethod
    def get(cls) -> "QueryScheduler":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def shutdown(cls) -> None:
        with cls._ilock:
            cls._instance = None

    # -----------------------------------------------------------------------
    @staticmethod
    def _budget_for(conf: C.RapidsConf, dm) -> int:
        declared = int(conf[C.SCHED_QUERY_BUDGET])
        if declared > 0:
            return declared
        maxq = max(1, int(conf[C.SCHED_MAX_CONCURRENT]))
        return max(1, dm.budget // maxq)

    def admit(self, qc: QueryContext, conf: C.RapidsConf) -> bool:
        """Admit `qc` (True) or queue until admissible; raises
        `TpuQueryRejected` when the queue is full or the queue deadline
        passes, and `TpuQueryTimeout` if the query is cancelled while
        queued.  False = scheduler disabled (unmanaged query)."""
        if not conf[C.SCHED_ENABLED]:
            return False
        from spark_rapids_tpu.memory.device_manager import DeviceManager
        from spark_rapids_tpu.utils import profile as P
        dm = DeviceManager.get()
        budget = self._budget_for(conf, dm)
        maxq = max(1, int(conf[C.SCHED_MAX_CONCURRENT]))
        qc.budget_bytes = budget
        with self._cv:
            if self._try_admit_locked(qc, budget, maxq, dm):
                P.event(P.EV_QUERY_ADMITTED, query=qc.query_id,
                        budget_bytes=budget, queued_ms=0)
                return True
            depth = int(conf[C.SCHED_QUEUE_DEPTH])
            if len(self._queue) >= max(0, depth):
                self._stats["rejected"] += 1
                snap = self._snapshot_locked(dm)
                P.event(P.EV_QUERY_REJECTED, query=qc.query_id,
                        budget_bytes=budget, **snap)
                raise TpuQueryRejected(
                    f"query {qc.query_id} rejected: admission queue is "
                    f"full ({len(self._queue)}/{depth} waiting, "
                    f"{snap['admitted_queries']} queries admitted "
                    f"holding {snap['admitted_bytes']}/{dm.budget} "
                    f"budget bytes).  Retry later, raise "
                    f"{C.SCHED_QUEUE_DEPTH.key}, or lower "
                    f"{C.SCHED_QUERY_BUDGET.key} "
                    f"(requested {budget} bytes).")
            entry = _QueueEntry(qc, budget, maxq)
            self._queue.append(entry)
            self._stats["queued"] += 1
            self._stats["max_queue_depth"] = max(
                self._stats["max_queue_depth"], len(self._queue))
            position = len(self._queue)
            P.event(P.EV_QUERY_QUEUED, query=qc.query_id,
                    budget_bytes=budget, position=position)
        return self._wait_admitted(entry, conf, dm)

    def _wait_admitted(self, entry: _QueueEntry, conf: C.RapidsConf,
                       dm) -> bool:
        """Park in the admission queue: bounded polls so cancellation is
        honored, a task-class heartbeat that beats as the queue drains
        (a queue making NO progress past the watchdog deadline trips a
        dump naming every admitted query), and the explicit
        `queueTimeout` bound."""
        from spark_rapids_tpu.utils import profile as P
        from spark_rapids_tpu.utils import watchdog as W
        qc = entry.qc
        timeout = float(conf[C.SCHED_QUEUE_TIMEOUT])
        deadline = entry.enqueued + timeout
        last_pos = None
        hb = W.heartbeat(
            f"query-queue:{qc.query_id}", kind="task", conf=conf,
            details=lambda: self.describe())
        try:
            with P.span(f"admission-queue:{qc.query_id}",
                        cat=P.CAT_QUEUE):
                with self._cv:
                    while True:
                        if entry.admitted:
                            waited = (time.monotonic()
                                      - entry.enqueued) * 1e3
                            self._stats["longest_queue_wait_ms"] = max(
                                self._stats["longest_queue_wait_ms"],
                                int(waited))
                            P.event(P.EV_QUERY_ADMITTED,
                                    query=qc.query_id,
                                    budget_bytes=entry.budget,
                                    queued_ms=int(waited))
                            return True
                        try:
                            pos = self._queue.index(entry) + 1
                        except ValueError:
                            pos = 0
                        if pos != last_pos:
                            hb.beat()      # queue progress, not a hang
                            last_pos = pos
                        now = time.monotonic()
                        if qc.token.cancelled or now >= deadline:
                            self._remove_locked(entry)
                            if qc.token.cancelled:
                                qc.token.check()  # raises TpuQueryTimeout
                            self._stats["queue_timeouts"] += 1
                            self._stats["rejected"] += 1
                            snap = self._snapshot_locked(dm)
                            P.event(P.EV_QUERY_REJECTED, query=qc.query_id,
                                    budget_bytes=entry.budget,
                                    timeout_s=timeout, **snap)
                            raise TpuQueryRejected(
                                f"query {qc.query_id} rejected: spent "
                                f"{timeout:.1f}s "
                                f"({C.SCHED_QUEUE_TIMEOUT.key}) in the "
                                f"admission queue at position {pos} "
                                f"({snap['admitted_queries']} queries "
                                f"admitted holding "
                                f"{snap['admitted_bytes']}/{dm.budget} "
                                "budget bytes).")
                        self._cv.wait(min(0.05, max(0.0,
                                                    deadline - now)))
        finally:
            hb.close()
            with self._cv:
                self._remove_locked(entry)

    def _try_admit_locked(self, qc: QueryContext, budget: int,
                          maxq: int, dm) -> bool:
        if len(dm.admissions()) >= maxq:
            return False
        if not dm.try_admit(qc.query_id, budget):
            return False
        qc.admitted = True
        self._stats["admitted"] += 1
        return True

    def _remove_locked(self, entry: _QueueEntry) -> None:
        try:
            self._queue.remove(entry)
        except ValueError:
            pass

    def release(self, qc: QueryContext) -> None:
        """Return `qc`'s admission slot and drain the queue head(s)."""
        from spark_rapids_tpu.memory.device_manager import DeviceManager
        if not qc.admitted:
            return
        dm = DeviceManager.get()
        with self._cv:
            dm.release_admission(qc.query_id)
            qc.admitted = False
            # FIFO drain: admit from the head while it fits.  Stopping
            # at the first non-admissible entry keeps arrival order —
            # a large query at the head is not starved by small ones
            # slipping past it forever.
            for entry in list(self._queue):
                if entry.admitted:
                    continue
                if not self._try_admit_locked(entry.qc, entry.budget,
                                              entry.max_queries, dm):
                    break
                entry.admitted = True
            self._cv.notify_all()

    # -----------------------------------------------------------------------
    def _snapshot_locked(self, dm) -> dict:
        adm = dm.admissions()
        return {"admitted_queries": len(adm),
                "admitted_bytes": sum(adm.values()),
                "queue_depth": len(self._queue)}

    def stats(self) -> dict:
        with self._cv:
            return dict(self._stats)

    def queue_depth(self) -> int:
        """Queries parked in the admission queue RIGHT NOW (telemetry
        gauge + the sampler's queue_wait classification)."""
        with self._cv:
            return len(self._queue)

    def describe(self) -> str:
        """One-line admission state for watchdog dumps / heartbeats."""
        from spark_rapids_tpu.memory.device_manager import DeviceManager
        try:
            dm = DeviceManager.get()
            with self._cv:
                adm = dm.admissions()
                queued = [(e.qc.query_id, e.budget)
                          for e in self._queue]
            return (f"admitted={adm} admitted_bytes="
                    f"{sum(adm.values())}/{dm.budget} queued={queued}")
        except Exception as e:  # noqa: BLE001 — diagnostics only
            return f"<unavailable: {e}>"


# ---------------------------------------------------------------------------
class QueryScope:
    """Query ownership for a driver-side entry point: if the calling
    thread has no live QueryContext, creates one, begins its profile
    (BEFORE admission, so queue wait is a first-class span/category in
    the query's own breakdown; a plan phase that accelerate() parked
    on `plan` is resumed there), and admits it; otherwise a no-op that
    defers to the enclosing scope.  `plan/overrides.collect` holds one
    around the whole drive (deopt retries, the AQE stage loop, partial
    CPU plans) and `TpuExec.collect` holds one per direct collect."""

    __slots__ = ("qc", "owns", "prof_owner", "_prev_tls")

    def __init__(self, conf: Optional[C.RapidsConf] = None, plan=None):
        from spark_rapids_tpu.utils import profile as P
        self.qc = current()
        self.owns = self.qc is None
        self.prof_owner = None
        self._prev_tls = None
        if not self.owns:
            return
        conf = conf if conf is not None else C.get_active_conf()
        self.qc = QueryContext(conf)
        self._prev_tls = getattr(_TLS, "qc", None)
        _TLS.qc = self.qc
        # engine-wide telemetry (utils/telemetry.py): lazy-started on
        # the first collect whose conf enables it; the in-flight query
        # count feeds the utilization sampler's idle/host attribution
        from spark_rapids_tpu.utils import telemetry as T
        T.maybe_start(conf)
        T.note_query_begin()
        # kernel attribution (utils/kernelprof.py): same lazy-start
        # discipline — sticky process-wide enable on the first query
        # whose conf asks for it, one global read + one lookup when off
        from spark_rapids_tpu.utils import kernelprof as KP
        KP.maybe_enable(conf)
        try:
            self.prof_owner = P.begin_query(conf, plan)
            QueryScheduler.get().admit(self.qc, conf)
        except BaseException as e:
            self.close(error=e)
            raise

    def close(self, error: Optional[BaseException] = None,
              end_profile: bool = True) -> None:
        """Release admission + the thread-local installation (owner
        only).  `end_profile=False` when the caller already assembled
        the QueryProfile itself (TpuExec.collect orders it around its
        metrics charge)."""
        if not self.owns:
            return
        self.owns = False
        from spark_rapids_tpu.utils import profile as P
        from spark_rapids_tpu.utils import telemetry as T
        try:
            if end_profile:
                P.end_query(self.prof_owner, self.qc.report_plan,
                            error=error)
        finally:
            QueryScheduler.get().release(self.qc)
            T.note_query_end()
            _TLS.qc = self._prev_tls


class CollectScope:
    """The per-collect handshake `TpuExec.collect` drives.  Not a
    context manager: collect needs the outermost flag and the profile
    owner between its own finally steps."""

    __slots__ = ("plan", "qc", "owns_qc", "prof_owner", "outermost",
                 "_qscope", "_plan_locked")

    def __init__(self, plan):
        self.plan = plan
        self._qscope = QueryScope(plan=plan)
        self.qc = self._qscope.qc
        self.owns_qc = self._qscope.owns
        self.prof_owner = self._qscope.prof_owner
        self._plan_locked = False
        entered = False
        try:
            self.outermost = self.qc.enter_collect()
            entered = True
            if self.outermost:
                # serialize collects over the SAME plan instance: its
                # CommonSubplanExec caches, metrics, and release hooks
                # are instance state.  Distinct plan instances (the
                # normal concurrent-session case) run in parallel.
                self._lock_plan()
                if self.qc.report_plan is None:
                    self.qc.report_plan = plan
        except BaseException:
            if entered:
                # a cancelled plan-lock wait must not leave the depth
                # bumped — a NESTED collect's enclosing query would
                # never see its own outermost exit again
                self.qc.exit_collect()
            self._qscope.close(end_profile=True)
            raise

    def _lock_plan(self) -> None:
        lock = getattr(self.plan, "_plan_lock", None)
        if lock is None:
            return
        while not lock.acquire(timeout=0.1):
            self.qc.token.check()
        self._plan_locked = True

    def finish_collect(self) -> bool:
        """Decrement the query's collect depth; True = this was the
        outermost collect (caller releases plan state + assembles the
        profile before `close`)."""
        return self.qc.exit_collect()

    def close(self) -> None:
        """Release the plan lock and, for the qc owner, the admission
        slot and the thread-local installation (the profile was ended
        by collect itself, ordered after the metrics charge)."""
        if self._plan_locked:
            self.plan._plan_lock.release()
            self._plan_locked = False
        self._qscope.close(end_profile=False)


# ---------------------------------------------------------------------------
# whole-mesh dispatch gate (exec/spmd.py SPMD gang dispatches)
#
# Task-level device sharing is the TpuSemaphore's job, and per-query
# HBM admission is the ledger's — but a whole-mesh program (an SPMD
# gang dispatch, a mesh-exchange all-to-all, or the slicing of their
# sharded outputs) occupies EVERY device of the active mesh at once.
# Two threads enqueueing whole-mesh programs concurrently can invert
# the per-device queue order (program A before B on device 0, B before
# A on device 4) and DEADLOCK the collective rendezvous — observed on
# the 8-device virtual CPU mesh with one query in the hand-rolled
# exchange lane and another in an SPMD gang.  The gate serializes
# every whole-mesh enqueue region process-wide, with the same
# cancellable bounded-poll discipline every other engine wait uses: a
# query cancelled while parked here unwinds instead of queueing a
# dispatch nobody will consume.  Reentrant, so a lane that composes
# whole-mesh steps (count + data phases) can hold it across both.

_MESH_GATE = threading.RLock()
_MESH_GATE_STATS = {"dispatches": 0, "longest_wait_ms": 0}
_MESH_GATE_STATS_LOCK = threading.Lock()


@contextmanager
def whole_mesh_dispatch(label: str = "spmd"):
    """Hold the process-wide whole-mesh dispatch slot for one SPMD gang
    dispatch.  Bounded-poll acquisition honors the calling query's
    CancelToken; stats feed scheduler_stats()/bench summaries."""
    from spark_rapids_tpu.utils import watchdog as W
    t0 = time.monotonic()
    while not _MESH_GATE.acquire(timeout=0.05):
        W.check_cancelled()
    waited_ms = int((time.monotonic() - t0) * 1e3)
    with _MESH_GATE_STATS_LOCK:
        _MESH_GATE_STATS["dispatches"] += 1
        _MESH_GATE_STATS["longest_wait_ms"] = max(
            _MESH_GATE_STATS["longest_wait_ms"], waited_ms)
    try:
        yield
    finally:
        _MESH_GATE.release()


def mesh_gate_stats() -> dict:
    with _MESH_GATE_STATS_LOCK:
        return dict(_MESH_GATE_STATS)


# ---------------------------------------------------------------------------
# plan-fingerprint result cache
class _CacheKey:
    """Equality = structural fingerprint + conf fingerprint + IDENTITY
    of the source data objects.  Holding strong refs to the sources
    pins their ids for the entry's lifetime, so a recycled id can never
    alias a dead source."""

    __slots__ = ("structure", "conf_fp", "sources", "_hash")

    def __init__(self, structure: str, conf_fp: tuple, sources: tuple):
        self.structure = structure
        self.conf_fp = conf_fp
        self.sources = sources
        self._hash = hash((structure, conf_fp,
                           tuple(id(s) for s in sources)))

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return (isinstance(other, _CacheKey)
                and self.structure == other.structure
                and self.conf_fp == other.conf_fp
                and len(self.sources) == len(other.sources)
                and all(a is b for a, b in zip(self.sources,
                                               other.sources)))


def _fingerprint_node(node, sources: list) -> Optional[str]:
    """Structural fingerprint of one plan node, collecting source-data
    identity objects into `sources`.  None = this plan is not cacheable
    (an unrecognized leaf / stateful wrapper) — never guess."""
    from spark_rapids_tpu.exec.base import (CommonSubplanExec, TpuExec)
    from spark_rapids_tpu.exec.basic import LocalBatchSource, RangeExec
    if not isinstance(node, TpuExec):
        return None
    if isinstance(node, LocalBatchSource):
        # prefer the plan-build-stable identity (the backing pandas
        # partitions, stamped by the CpuSource converter): re-planning
        # the same query uploads FRESH device batches, but the session's
        # source frames persist — those are what "same data" means
        ident = getattr(node, "source_identity", None)
        sources.extend(ident if ident is not None
                       else (b for part in node.partitions
                             for b in part))
        return (f"LocalBatchSource({len(node.partitions)} parts,"
                f"{node.output_schema()})")
    if isinstance(node, RangeExec):
        return node.describe()
    if type(node).__name__ == "TpuFileSourceScanExec":
        # file identity: path + per-file (size, mtime) so a rewritten
        # file invalidates the entry
        try:
            stats = []
            for part in node.scan.partitions:
                for f in part.files:
                    st = os.stat(f.path)
                    stats.append((f.path, st.st_size, st.st_mtime_ns))
            return f"{node.describe()}::{sorted(stats)!r}"
        except Exception:  # noqa: BLE001 — unstatable source: no cache
            return None
    if isinstance(node, CommonSubplanExec) or node.children:
        kids = []
        for c in node.children:
            fp = _fingerprint_node(c, sources)
            if fp is None:
                return None
            kids.append(fp)
        return f"{node.describe()}[{';'.join(kids)}]"
    return None  # unrecognized leaf (stage wrappers, transitions, ...)


def result_cache_key(plan, conf: C.RapidsConf) -> Optional[_CacheKey]:
    """Cache key for a fully-TPU plan under `conf`, or None when result
    caching is disabled / the plan is not fingerprintable."""
    if not conf[C.RESULT_CACHE_ENABLED]:
        return None
    if int(conf[C.RESULT_CACHE_MAX_BYTES]) <= 0:
        return None
    sources: list = []
    try:
        structure = _fingerprint_node(plan, sources)
    except Exception:  # noqa: BLE001 — a fingerprint failure means
        return None    # "don't cache", never "fail the query"
    if structure is None:
        return None
    return _CacheKey(structure, conf.fingerprint(), tuple(sources))


class ResultCache:
    """Byte-bounded LRU of collected query results (pandas frames)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._bytes = 0
        self._stats = {"hits": 0, "misses": 0, "evictions": 0,
                       "stores": 0}

    @staticmethod
    def _df_bytes(df) -> int:
        try:
            return int(df.memory_usage(index=True, deep=True).sum())
        except Exception:  # noqa: BLE001
            return 1 << 20

    def get(self, key: _CacheKey):
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                self._stats["misses"] += 1
                return None
            self._entries.move_to_end(key)
            self._stats["hits"] += 1
            df, _ = hit
        # copy OUTSIDE the lock: callers may mutate the returned frame
        return df.copy(deep=True)

    def put(self, key: _CacheKey, df, max_bytes: int) -> None:
        nbytes = self._df_bytes(df)
        if nbytes > max_bytes:
            return  # larger than the whole cache: not worth holding
        frozen = df.copy(deep=True)
        with self._lock:
            if key in self._entries:
                _, old = self._entries.pop(key)
                self._bytes -= old
            self._entries[key] = (frozen, nbytes)
            self._bytes += nbytes
            self._stats["stores"] += 1
            while self._bytes > max_bytes and self._entries:
                _, (_, evicted) = self._entries.popitem(last=False)
                self._bytes -= evicted
                self._stats["evictions"] += 1

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    def stats(self) -> dict:
        with self._lock:
            return {**self._stats, "entries": len(self._entries),
                    "bytes": self._bytes}


_RESULT_CACHE = ResultCache()


def result_cache() -> ResultCache:
    return _RESULT_CACHE


def scheduler_stats() -> dict:
    """Scheduler + result-cache counters for bench/CI summary lines."""
    return {**QueryScheduler.get().stats(),
            "result_cache": _RESULT_CACHE.stats(),
            "mesh_gate": mesh_gate_stats()}
