"""Async pipelined execution: bounded prefetch between pipeline stages.

The engine is host-driven: Python pulls batches through operator
iterators while all per-batch compute runs in XLA executables.  Fully
synchronous pulling serializes the three resources a query actually
uses — host orchestration (decode, split bookkeeping, upload staging),
the host->device transfer, and device kernels — so the TPU idles while
Python works and vice versa.  `PrefetchIterator` breaks that lockstep at
pipeline breaks (scan->compute, both sides of a shuffle exchange,
coalesce boundaries, AQE stage materialization): a background producer
thread runs the upstream iterator up to `prefetchDepth` batches ahead of
the consumer through a bounded queue, the same overlap the reference
gets from `MultiFileThreadPoolFactory` + the CUDA stream (we only had it
inside io/scan.py's host buffering).

Discipline (the parts that make this safe rather than just concurrent):

* **Bounded depth** — the queue holds at most `prefetchDepth` batches,
  so a fast producer cannot flood HBM; backpressure is the queue block.
* **Semaphore** — a producer blocked on a full queue NEVER holds the TPU
  semaphore: it yields its task's hold for the duration of the block
  (`TpuSemaphore.yielded`, the PR 1 spill discipline) so concurrent
  tasks keep the accelerator busy while this one is parked.
* **Task identity** — the producer runs under the creating thread's
  `TaskContext` when one exists (one task, helper thread — the
  reference's multithreaded reader model), else under a fresh private
  context that is force-completed (semaphore released) on thread exit.
* **Conf propagation** — the session conf is thread-local; the producer
  re-installs the creator's conf so upstream conf reads see the same
  values the plan was built with.
* **Error / cancellation propagation** — a producer exception is
  re-raised at the consumer's pull point (so OOM split-and-retry and
  deopt recovery fire on the consuming side exactly as they would
  synchronously), and closing the consumer cancels the producer and
  closes the source iterator so upstream cleanup (shuffle reader
  release, file handles) still runs.
* **Lazy start** — the producer thread starts on the consumer's first
  pull, not at plan build: `execute_partitions()` constructs every
  partition's iterator eagerly, and starting all producers there would
  turn plan construction into unbounded whole-plan concurrency.
"""
from __future__ import annotations

import itertools
import logging
import queue
import threading
import time
from typing import Iterable, Iterator, Optional

from spark_rapids_tpu.utils import metrics as M

log = logging.getLogger("spark_rapids_tpu.pipeline")

#: end-of-stream sentinel (errors ride on `self._error`, set before this)
_DONE = object()

#: task ids for producers created outside any task context; offset far
#: above real task-attempt ids so the two never collide in the
#: semaphore's refcount table
_PRODUCER_TASK_IDS = itertools.count(1 << 40)

#: poll granularity for cancellable blocking queue ops; latency is only
#: paid on the (rare) full/empty-with-dead-producer edges
_POLL_S = 0.05

#: how long close()/_finish wait for a producer thread before declaring
#: it leaked (module-level so the watchdog suite can shrink it)
_JOIN_TIMEOUT_S = 10.0

# process-wide stats (read beside wall clock they show overlap, not
# just totals; leaked_producers
# counts threads that survived the close() join — surfaced in the
# watchdog dump, because a leaked producer is exactly the kind of
# wedged activity the watchdog exists to name)
_STATS_LOCK = threading.Lock()
_STATS = {"producers": 0, "hits": 0, "stalls": 0, "wait_ns": 0,
          "blocked_puts": 0, "leaked_producers": 0}

# LIVE occupancy (vs the cumulative counters above): how many consumers
# are blocked on an empty queue / producers parked on a full one RIGHT
# NOW — the telemetry sampler's pipeline_stall classification.  Bumped
# only on the (already slow) blocking edges, never the hit path.
_LIVE_STATS = {"stalled_consumers": 0, "blocked_producers": 0}


def pipeline_stats() -> dict:
    with _STATS_LOCK:
        return dict(_STATS)


def pipeline_live() -> dict:
    with _STATS_LOCK:
        return dict(_LIVE_STATS)


def _bump_live(name: str, delta: int) -> None:
    with _STATS_LOCK:
        _LIVE_STATS[name] += delta


def reset_pipeline_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _bump(name: str, value: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[name] += value


class PrefetchIterator:
    """Depth-bounded background prefetch over a batch iterator.

    Iterator protocol on the consumer side; the source runs on a
    producer thread started at the first pull.  `close()` (also invoked
    by GC) cancels the producer, drains the queue, and closes the
    source."""

    def __init__(self, source: Iterable, depth: int,
                 label: str = "pipeline", metrics=None, conf=None):
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.memory.semaphore import TaskContext
        assert depth > 0
        self._source = iter(source)
        self._q: "queue.Queue" = queue.Queue(maxsize=int(depth))
        self._label = label
        self._metrics = metrics
        self._conf = conf if conf is not None else C.get_active_conf()
        #: creator's task identity, shared with the producer thread when
        #: present (same task, helper thread)
        self._ctx = TaskContext.get()
        #: thread-local deopt-retry flag, propagated so fast paths the
        #: producer executes still bypass themselves on the final
        #: guaranteed-valid attempt (iterators are rebuilt per attempt,
        #: so construction-time capture is exact)
        from spark_rapids_tpu.utils import checks as CK
        self._retrying = CK.is_retrying()
        #: the creating query's context AND cancel token: the producer
        #: thread runs scoped to the creator's query, so its conf
        #: reads, deferred checks, profile events, semaphore fair-share
        #: group, and cancellation all belong to the RIGHT query —
        #: never a concurrent session's
        from spark_rapids_tpu.exec import scheduler as S
        from spark_rapids_tpu.utils import watchdog as W
        self._qc = S.current()
        self._token = W.current_token()
        #: creator's span context (None unless the query is profiled):
        #: the producer thread attaches here so its spans parent under
        #: the pipeline break that spawned it, not a detached root
        from spark_rapids_tpu.utils import profile as P
        self._span_ref = P.current_ref()
        self._hb = None
        self._closed = threading.Event()
        #: test-facing: set while the producer is parked on a full queue
        #: (the window in which it must not hold the TPU semaphore)
        self.blocked = threading.Event()
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._done = False

    # -- consumer side ------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        self._ensure_started()
        try:
            item = self._q.get_nowait()
            _bump("hits")
            if self._metrics is not None:
                self._metrics.add(M.PREFETCH_HITS, 1)
        except queue.Empty:
            item = self._wait_for_item()
        if item is _DONE:
            self._done = True
            self._finish()
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            raise StopIteration
        return item

    def _wait_for_item(self):
        from spark_rapids_tpu.utils import profile as P
        t0 = time.perf_counter_ns()
        # a stalled pull is exactly the overlap loss the profile's
        # breakdown wants to name; already off the hot path (we only
        # get here when the queue was empty), and a no-op unprofiled
        sp = P.span(f"pipeline-wait:{self._label}", cat=P.CAT_WAIT) \
            if P.tracer() is not None else P._NULL_SPAN
        _bump_live("stalled_consumers", 1)
        try:
            with sp:
                while True:
                    try:
                        return self._q.get(timeout=_POLL_S)
                    except queue.Empty:
                        if self._token.cancelled:
                            # watchdog cancellation: release what the
                            # producer buffered before surfacing, so the
                            # failed query pins nothing
                            self.close()
                            self._token.check()
                        t = self._thread
                        if t is None or not t.is_alive():
                            # producer exited: drain the put/exit race,
                            # then report end-of-stream (error checked
                            # by caller)
                            try:
                                return self._q.get_nowait()
                            except queue.Empty:
                                return _DONE
        finally:
            _bump_live("stalled_consumers", -1)
            waited = time.perf_counter_ns() - t0
            _bump("stalls")
            _bump("wait_ns", waited)
            if self._metrics is not None:
                self._metrics.add(M.PREFETCH_STALLS, 1)
                self._metrics.add(M.PIPELINE_WAIT_TIME, waited)

    def close(self) -> None:
        """Cancel the producer and release everything it buffered."""
        self._done = True
        self._closed.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._join_or_leak()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _finish(self) -> None:
        self._join_or_leak()

    def _join_or_leak(self) -> None:
        """Join the producer; a thread that survives the bounded join
        is LEAKED, not silently forgotten: it is counted in the
        process-wide pipeline stats (surfaced in the watchdog dump)
        and its stack is logged so the wedged frame is attributable."""
        t = self._thread
        if (t is None or not t.is_alive()
                or t is threading.current_thread()):
            return
        t.join(timeout=_JOIN_TIMEOUT_S)
        if not t.is_alive():
            return
        self._thread = None  # joining again later cannot succeed
        _bump("leaked_producers")
        from spark_rapids_tpu.utils import watchdog as W
        stack = W.thread_stack(t.ident)
        log.warning(
            "prefetch producer %s survived the %.0fs close() join and "
            "was leaked (source iterator is wedged); stack:\n%s",
            t.name, _JOIN_TIMEOUT_S, stack or "<unavailable>")

    # -- producer side ------------------------------------------------------
    def start(self) -> None:
        """Start producing now, ahead of the first pull."""
        self._ensure_started()

    def _ensure_started(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._produce, daemon=True,
                name=f"tpu-prefetch-{self._label}")
            _bump("producers")
            self._thread.start()

    def _produce(self) -> None:
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.memory.semaphore import TaskContext
        from spark_rapids_tpu.utils import checks as CK
        from spark_rapids_tpu.utils import watchdog as W
        if self._retrying:
            CK.set_retrying(True)
        own_ctx = None
        if self._ctx is not None:
            TaskContext.set_current(self._ctx)
        else:
            own_ctx = TaskContext(next(_PRODUCER_TASK_IDS))
            TaskContext.set_current(own_ctx)
        # thread the query's cancel token + context through the
        # TaskContext so downstream checks on this thread (and any
        # helper threads it spawns) reach the right query
        cur = TaskContext.get()
        if cur is not None and getattr(cur, "cancel_token", None) is None:
            cur.cancel_token = self._token
        if cur is not None and getattr(cur, "query_ctx", None) is None:
            cur.query_ctx = self._qc
        from spark_rapids_tpu.exec import scheduler as S
        from spark_rapids_tpu.utils import profile as P
        try:
            with S.scoped(self._qc), C.session(self._conf), \
                    P.attach(self._span_ref), \
                    P.span(f"producer:{self._label}", cat=P.CAT_PIPELINE):
                hb = W.heartbeat(f"producer:{self._label}",
                                 kind="task",
                                 details=lambda: f"queue depth "
                                 f"{self._q.qsize()}/{self._q.maxsize}")
                self._hb = hb
                try:
                    with hb:
                        for item in self._source:
                            hb.beat()
                            W.maybe_hang("producer")
                            if not self._put(item):
                                return  # consumer closed
                except BaseException as e:  # noqa: BLE001 — re-raised
                    self._error = e         # at the consumer's pull
                self._put(_DONE)
        finally:
            self._hb = None
            try:
                close = getattr(self._source, "close", None)
                if close is not None:
                    close()
            except Exception:
                pass
            if own_ctx is not None:
                # private task identity: force-release any semaphore
                # hold the source's device work acquired
                own_ctx.complete()
            TaskContext.set_current(None)

    def _put(self, item) -> bool:
        """Enqueue with backpressure.  False = consumer cancelled.  A
        producer parked on a full queue must not hold the TPU semaphore
        — its task's hold is yielded for the duration of the block."""
        try:
            self._q.put_nowait(item)
            return True
        except queue.Full:
            pass
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        from contextlib import nullcontext
        _bump("blocked_puts")
        _bump_live("blocked_producers", 1)
        self.blocked.set()
        hb = self._hb
        try:
            # parked on a full queue: this is the CONSUMER's stall, not
            # ours — pause the producer heartbeat so backpressure is
            # never mistaken for a hang, and watch the cancel token so
            # a cancelled query's producer exits instead of parking
            # forever on a queue nobody will drain
            with TpuSemaphore.get().yielded(), \
                    (hb.pause() if hb is not None else nullcontext()):
                while not self._closed.is_set():
                    if self._token.cancelled:
                        return False
                    try:
                        self._q.put(item, timeout=_POLL_S)
                        return True
                    except queue.Full:
                        continue
                return False
        finally:
            _bump_live("blocked_producers", -1)
            self.blocked.clear()


def maybe_prefetch(source: Iterable, label: str = "pipeline",
                   metrics=None, conf=None,
                   depth: Optional[int] = None) -> Iterator:
    """Wrap `source` in a PrefetchIterator when the session conf enables
    pipelining (and `depth`/prefetchDepth > 0); otherwise return it
    unwrapped.  Call at iterator-construction time on the thread that
    carries the session conf (plan build / execute_partitions)."""
    from spark_rapids_tpu import config as C
    conf = conf if conf is not None else C.get_active_conf()
    if not conf[C.PIPELINE_ENABLED]:
        return iter(source)
    if depth is None:
        depth = int(conf[C.PIPELINE_PREFETCH_DEPTH])
    if depth <= 0:
        return iter(source)
    return PrefetchIterator(source, depth, label=label, metrics=metrics,
                            conf=conf)


def drain_partitions(partitions: list, label: str = "partition",
                     metrics=None) -> list[list]:
    """Every batch of every partition, partition by partition.  Under an
    active mesh with one partition a chip the partitions are drained
    TOGETHER, one producer thread a chip (a task a chip, as one executor
    a chip runs them): their programs lie on different chips, so their
    dispatches overlap, and a cold kernel's per-chip copies (independent
    XLA compilations, which release the GIL) compile side by side and
    not one after the other.  Anywhere else, or with pipelining off: one
    after the other on the caller's thread, as a plain loop would."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.parallel import mesh as PM
    if (PM.partition_devices(len(partitions)) is None
            or not C.get_active_conf()[C.PIPELINE_ENABLED]):
        return [list(it) for it in partitions]
    # unbounded: the caller keeps every batch anyway (a barrier)
    tasks = [PrefetchIterator(it, 1 << 30, label=label, metrics=metrics)
             for it in partitions]
    try:
        for t in tasks:
            t.start()
        return [list(t) for t in tasks]
    finally:
        for t in tasks:
            t.close()

