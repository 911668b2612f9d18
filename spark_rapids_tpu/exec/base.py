"""Physical operator base (reference `GpuExec.scala:58-123`).

A `TpuExec` produces an iterator of `ColumnarBatch` — the TPU analog of
`doExecuteColumnar(): RDD[ColumnarBatch]`.  The engine is host-driven like
Spark tasks: Python orchestrates batch flow, while all per-batch compute
runs in jitted XLA executables.

The kernel compile cache is the central XLA-fit mechanism (SURVEY.md §7
hard part (a)): executables are keyed on (plan node, batch shape signature)
so ragged Spark batches hit a small set of bucketed compilations.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import ColumnVector
from spark_rapids_tpu.exprs.base import EvalContext, Expression
from spark_rapids_tpu.utils import kernelprof as KP
from spark_rapids_tpu.utils import metrics as M


# ---------------------------------------------------------------------------
# coalesce goals (reference GpuCoalesceBatches.scala:91-113)
@dataclasses.dataclass(frozen=True)
class CoalesceGoal:
    pass


@dataclasses.dataclass(frozen=True)
class TargetSize(CoalesceGoal):
    bytes: int


@dataclasses.dataclass(frozen=True)
class RequireSingleBatch(CoalesceGoal):
    pass


def max_goal(a: Optional[CoalesceGoal], b: Optional[CoalesceGoal]
             ) -> Optional[CoalesceGoal]:
    if isinstance(a, RequireSingleBatch) or isinstance(b, RequireSingleBatch):
        return RequireSingleBatch()
    if isinstance(a, TargetSize) and isinstance(b, TargetSize):
        return TargetSize(max(a.bytes, b.bytes))
    return a or b


# ---------------------------------------------------------------------------
#: the longest kernel name: a fused stage's member list is cut to it
KERNEL_NAME_MAX = 64


def kernel_name(label: str) -> str:
    """A `kp_meta` label as an identifier: the one vocabulary of
    kernel names, read on the host (utils/kernelprof) and on the device
    (the profiler's `XLA Modules` line reads `jit_<name>(<hash>)`)."""
    return re.sub(r"\W", "_", label)[:KERNEL_NAME_MAX]


def named_jit(label: str, fn: Optional[Callable] = None, **jit_kwargs):
    """`jax.jit` under the kernel's own name instead of `kernel` /
    `<lambda>`, so a device trace tells the operators' programs apart.
    `@named_jit("filter")` decorates; `named_jit("topn-k", fn)` wraps.
    The name is part of the module the persistent compile cache
    hashes: renaming a kernel compiles it once more.  `__qualname__`
    stays: it says where the kernel is defined."""
    def wrap(f):
        f.__name__ = kernel_name(label)
        return jax.jit(f, **jit_kwargs)
    return wrap if fn is None else wrap(fn)


# ---------------------------------------------------------------------------
def columns_signature(fields, cols) -> tuple:
    """Per-column shape signature entries for the compile cache:
    (dtype, char_cap, narrowed?)."""
    return tuple((f.dtype.id.value,
                  c.char_cap if f.dtype.is_string else 0,
                  c.narrow is not None)
                 for f, c in zip(fields, cols))


def batch_signature(batch: ColumnarBatch) -> tuple:
    """Shape signature for the compile cache: capacity + per-column
    (dtype, char_cap)."""
    return ((batch.capacity,)
            + columns_signature(batch.schema.fields, batch.columns)
            + (batch.sparse is not None,))


def mesh_cache_scope(mesh, axis: str, shardings=()) -> tuple:
    """Cache-key component for whole-mesh (SPMD) executables: the mesh
    shape, its device identity, the partitioned axis, and the sharding
    layout descriptors.  An SPMD program is specialized to all of these
    — a kernel compiled for one mesh/sharding must never be served for
    another, and (because this tuple appears in no per-partition key)
    SPMD and per-partition entries can never collide.  Device identity
    enters as ids, not Device objects, so a dead mesh is not pinned
    beyond its cached executables' LRU lifetime."""
    return ("mesh",
            tuple((name, int(n)) for name, n in mesh.shape.items()),
            tuple(d.id for d in mesh.devices.flat),
            axis,
            tuple(str(s) for s in shardings))


#: process-global executable store (bounded LRU): compiled kernels outlive
#: plan instances, so per-query plan rebuilds and AQE re-plans over the
#: same expressions hit warm executables instead of re-tracing
import collections
import threading

_GLOBAL_KERNELS: "collections.OrderedDict" = collections.OrderedDict()
_GLOBAL_KERNELS_LOCK = threading.Lock()
# one workload's operator x batch-shape set is well under this; XLA CPU
# clients have been observed to segfault with thousands of live loaded
# executables, so the LRU stays conservatively small.  Conf-overridable
# (spark.rapids.sql.kernelCache.maxEntries): fused-stage keys multiply
# cache pressure, so the bound and its eviction count are first-class.
_GLOBAL_KERNELS_MAX = 512
_GLOBAL_KERNELS_EVICTIONS = 0


def _kernel_cache_max_entries() -> int:
    try:
        from spark_rapids_tpu import config as C
        return max(1, int(C.get_active_conf()[C.KERNEL_CACHE_MAX_ENTRIES]))
    except Exception:  # noqa: BLE001 — conf layer unavailable in
        return _GLOBAL_KERNELS_MAX  # stripped-down test harnesses
#: single-flight registry: keys whose builder is currently tracing /
#: compiling on some thread (value: Event set when it lands or fails).
#: XLA compiles run seconds-to-minutes, so they must happen OUTSIDE
#: _GLOBAL_KERNELS_LOCK — but with pipelined execution two threads
#: routinely reach the same (exec, signature) miss together, and
#: compiling the same kernel twice wastes exactly the time pipelining
#: saves.  Losing a rare race anyway (event timeout, builder failure)
#: degrades to the benign double-compile, never to a wrong result.
_GLOBAL_KERNELS_BUILDING: dict = {}


def clear_kernel_cache() -> None:
    with _GLOBAL_KERNELS_LOCK:
        _GLOBAL_KERNELS.clear()


def kernel_cache_size() -> int:
    return len(_GLOBAL_KERNELS)


def kernel_cache_evictions() -> int:
    """LRU evictions since process start (bench summary surfaces this:
    a growing number means kernelCache.maxEntries is churning)."""
    return _GLOBAL_KERNELS_EVICTIONS


#: cumulative trace/compile accounting (telemetry registry): every
#: `_build_watched` builder run lands here, private-cache and global
#: alike, so compile cost is visible process-wide even when the profile
#: span layer is off
_COMPILE_STATS_LOCK = threading.Lock()
_COMPILE_NS_TOTAL = 0
_COMPILE_COUNT = 0


def kernel_cache_compiles() -> int:
    with _COMPILE_STATS_LOCK:
        return _COMPILE_COUNT


def kernel_cache_compile_ms() -> float:
    with _COMPILE_STATS_LOCK:
        return _COMPILE_NS_TOTAL / 1e6


class _ColdKernel:
    """A freshly built kernel until its first dispatch has returned:
    `jax.jit` compiles there and not in the builder, so that one call
    runs under `watchdog.compiling`.  Afterwards a plain forward.
    Attribute reads fall through to the kernel (`lower`, site-attached
    labels), as `kernelprof.WatchedKernel`'s do."""

    def __init__(self, label: str, fn):
        self._ck_label = label
        self._ck_fn = fn
        self._ck_cold = True

    def __call__(self, *args, **kwargs):
        if not self._ck_cold:
            return self._ck_fn(*args, **kwargs)
        from spark_rapids_tpu.utils import watchdog as W
        with W.compiling(self._ck_label):
            out = self._ck_fn(*args, **kwargs)
        self._ck_cold = False
        return out

    def __getattr__(self, name):
        return getattr(self._ck_fn, name)


class KernelCache:
    """Caches jitted executables per (scope, key, signature).

    With a `scope` (a structural fingerprint of the exec's bound
    expressions), entries live in the process-global LRU and are shared
    across plan instances.  Without one, the cache is private to the exec
    and dies with the plan (the pre-fingerprint behavior, still used by
    execs whose kernels close over non-fingerprintable state)."""

    def __init__(self, scope: tuple = None):
        self._scope = scope
        self._cache: dict = {} if scope is None else None

    @staticmethod
    def _build_watched(key, builder: Callable[[], Callable],
                       kp_entry=None):
        """Run the builder, and later the kernel's first dispatch (where
        a lazy `jax.jit` traces and compiles: seconds to minutes),
        under a compile-class watchdog heartbeat with the enclosing
        task's heartbeats paused (`watchdog.compiling`); the compile
        hang-injection site is in front so a wedged XLA compile is
        testable.  A profiled query additionally records the build as a
        span (cat 'compile'), so cold-start cost is attributable in the
        wall-clock breakdown; with kernel attribution on, the builder
        wall time also lands on the kernel's catalog entry
        (utils/kernelprof.py — the first DISPATCH is timed there
        separately)."""
        from spark_rapids_tpu.utils import profile as P
        from spark_rapids_tpu.utils import watchdog as W
        label = f"compile:{key!r:.120}"
        with W.compiling(label), P.span(label, cat=P.CAT_COMPILE):
            W.maybe_hang("compile")
            import time as _time
            t0 = _time.perf_counter_ns()
            try:
                fn = builder()
            finally:
                global _COMPILE_NS_TOTAL, _COMPILE_COUNT
                dt = _time.perf_counter_ns() - t0
                with _COMPILE_STATS_LOCK:
                    _COMPILE_NS_TOTAL += dt
                    _COMPILE_COUNT += 1
                if kp_entry is not None:
                    kp_entry.note_build(dt)
        return _ColdKernel(label, fn) if callable(fn) else fn

    def _kp_identity(self, key: tuple) -> tuple:
        """Catalog identity for a kernel of this cache: the structural
        scope when there is one; private caches get a process-unique
        token so unrelated private kernels never merge."""
        if self._scope is not None:
            return (self._scope, key)
        tok = self.__dict__.get("_kp_token")
        if tok is None:
            tok = self.__dict__["_kp_token"] = \
                ("private", KP.private_token())
        return (tok, key)

    def get_or_build(self, key: tuple, builder: Callable[[], Callable],
                     meta: Optional[dict] = None):
        """`meta` (only read while kernel attribution is enabled —
        build it via `TpuExec.kp_meta`, which returns None otherwise)
        attaches dispatch-site context to the kernel's catalog entry:
        a human label, the owning exec, and fused member names."""
        kp_on = KP.enabled()
        if self._scope is None:
            fn = self._cache.get(key)
            if fn is None:
                if kp_on:
                    ident = self._kp_identity(key)
                    fn = self._build_watched(key, builder,
                                             KP.entry_for(ident))
                    fn = KP.watch(ident, fn)
                else:
                    fn = self._build_watched(key, builder)
                self._cache[key] = fn
            elif kp_on and callable(fn) \
                    and not isinstance(fn, KP.WatchedKernel):
                # cached before attribution was enabled: upgrade in
                # place — the executable is already warm, so its first
                # wrapped dispatch is device time, not compile
                fn = KP.watch(self._kp_identity(key), fn, cold=False)
                self._cache[key] = fn
            if kp_on and meta is not None:
                KP.annotate(fn, meta)
            return fn
        from spark_rapids_tpu.utils import watchdog as W
        gk = (self._scope, key)
        claimed: Optional[threading.Event] = None
        while True:
            with _GLOBAL_KERNELS_LOCK:
                fn = _GLOBAL_KERNELS.get(gk)
                if fn is not None:
                    _GLOBAL_KERNELS.move_to_end(gk)
                    if kp_on and callable(fn) \
                            and not isinstance(fn, KP.WatchedKernel):
                        # cached before attribution was enabled:
                        # upgrade the shared entry in place (warm —
                        # its first dispatch is NOT a compile)
                        fn = KP.watch(gk, fn, cold=False)
                        _GLOBAL_KERNELS[gk] = fn
                if fn is None:
                    ev = _GLOBAL_KERNELS_BUILDING.get(gk)
                    if ev is None:
                        # claim the build; compile happens OUTSIDE the
                        # lock
                        claimed = threading.Event()
                        _GLOBAL_KERNELS_BUILDING[gk] = claimed
                        break
            if fn is not None:
                if kp_on and meta is not None:
                    KP.annotate(fn, meta)
                return fn
            # another thread is tracing/compiling this exact kernel:
            # wait for it instead of double-compiling, bounded by the
            # watchdog's compile deadline (and cancellable).  On wake,
            # either the entry is cached (loop hits it) or the builder
            # failed (loop re-claims and this thread builds).  On
            # TIMEOUT the builder may be wedged: fall through and
            # compile in THIS thread — a benign double compile, never
            # a proceed-with-missing-entry.
            if not W.cancellable_wait(ev, W.deadline_for("compile")):
                import logging
                logging.getLogger("spark_rapids_tpu.exec").warning(
                    "kernel single-flight wait exceeded the compile "
                    "deadline for %r; the claiming builder may be "
                    "wedged — compiling in this thread instead",
                    gk[1])
                break
        try:
            # builder runs OUTSIDE the lock
            fn = self._build_watched(key, builder, KP.entry_for(gk)) \
                if kp_on else self._build_watched(key, builder)
        except BaseException:
            if claimed is not None:
                with _GLOBAL_KERNELS_LOCK:
                    if _GLOBAL_KERNELS_BUILDING.get(gk) is claimed:
                        _GLOBAL_KERNELS_BUILDING.pop(gk, None)
                claimed.set()
            raise
        if kp_on:
            fn = KP.watch(gk, fn)
        max_entries = _kernel_cache_max_entries()
        with _GLOBAL_KERNELS_LOCK:
            _GLOBAL_KERNELS[gk] = fn
            global _GLOBAL_KERNELS_EVICTIONS
            while len(_GLOBAL_KERNELS) > max_entries:
                _GLOBAL_KERNELS.popitem(last=False)
                _GLOBAL_KERNELS_EVICTIONS += 1
            if claimed is not None and \
                    _GLOBAL_KERNELS_BUILDING.get(gk) is claimed:
                _GLOBAL_KERNELS_BUILDING.pop(gk, None)
        if claimed is not None:
            claimed.set()
        if kp_on and meta is not None:
            KP.annotate(fn, meta)
        return fn

    def __len__(self):
        if self._scope is None:
            return len(self._cache)
        with _GLOBAL_KERNELS_LOCK:
            return sum(1 for s, _ in _GLOBAL_KERNELS if s == self._scope)




def make_eval_context(columns: list[ColumnVector], capacity: int,
                      num_rows, mask=None) -> EvalContext:
    """`mask` (a sparse selection vector) overrides the prefix row mask —
    sparse-aware kernels fold deferred selections in for free."""
    row_mask = mask if mask is not None else (
        jnp.arange(capacity) < num_rows)
    return EvalContext(columns, capacity, num_rows, row_mask)


import itertools

_EXEC_IDS = itertools.count()


class TpuExec:
    """Base physical operator."""

    def __init__(self, *children: "TpuExec"):
        self._children = list(children)
        self.metrics = M.MetricSet()
        self.exec_id = next(_EXEC_IDS)
        #: serializes top-level collects over THIS plan instance: its
        #: CommonSubplanExec caches, metrics, and release hooks are
        #: instance state, so two sessions sharing one plan object run
        #: one at a time while distinct plan instances run concurrently
        self._plan_lock = threading.Lock()

    @property
    def kernels(self) -> KernelCache:
        """Compile cache, resolved lazily so `cache_scope()` can use
        subclass state set after base __init__.  Scoped execs share the
        bounded global store; unscoped ones keep a private cache."""
        kc = self.__dict__.get("_kernel_cache")
        if kc is None:
            scope = self.cache_scope()
            if scope is not None:
                scope = (type(self).__name__,) + tuple(scope)
            kc = KernelCache(scope)
            self.__dict__["_kernel_cache"] = kc
        return kc

    def cache_scope(self):
        """Structural fingerprint of everything this exec's kernels close
        over (bound expressions, modes, output schema).  None -> private
        cache (no cross-instance sharing)."""
        return None

    def kp_meta(self, label: str, members=None) -> Optional[dict]:
        """Dispatch-site metadata for the kernel catalog
        (utils/kernelprof.py): pass as `get_or_build(..., meta=...)`.
        Returns None — allocating nothing — when kernel attribution is
        off, so the disabled hot path stays byte-identical."""
        if not KP.enabled():
            return None
        return {"label": label, "owner_id": self.exec_id,
                "owner": self.describe()[:120],
                "members": list(members) if members else None}

    @property
    def children(self) -> list["TpuExec"]:
        return self._children

    @property
    def child(self) -> "TpuExec":
        return self._children[0]

    def output_schema(self) -> T.Schema:
        raise NotImplementedError

    # coalesce contract (reference GpuExec.coalesceAfter /
    # childrenCoalesceGoal)
    @property
    def coalesce_after(self) -> bool:
        return False

    def children_coalesce_goal(self) -> list[Optional[CoalesceGoal]]:
        return [None] * len(self._children)

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    def output_partition_count(self) -> int:
        """Planning-time partition count (outputPartitioning analog).
        MUST NOT execute anything — planners consult this."""
        if not self._children:
            return 1
        return self._children[0].output_partition_count()

    def execute_partitions(self) -> list[Iterator[ColumnarBatch]]:
        """Partitioned execution (RDD analog).  Default: operators that are
        partition-local map themselves over each child partition."""
        from spark_rapids_tpu.utils import profile as P
        kids = [c.execute_partitions() for c in self._children]
        if not kids:
            return [P.wrap_operator(self, 0, self.execute_columnar())]
        n = len(kids[0])
        return [P.wrap_operator(
                    self, i, self._execute_partition(
                        i, [k[i] for k in kids]))
                for i in range(n)]

    def _execute_partition(self, idx: int, child_iters
                           ) -> Iterator[ColumnarBatch]:
        # default: single-child partition-local operators override
        # execute_columnar using self.child; rebuild with a shim child.
        raise NotImplementedError(
            f"{type(self).__name__} does not support partitioned execution")

    #: bounded deopt attempts: intermediate retries may take optimistic
    #: fast paths with ESCALATED parameters (e.g. a ×4'd group-compact
    #: width) and fail again; only the LAST runs with every fast path
    #: forced off (is_retrying) for a guaranteed-valid result.  The old
    #: single-retry scheme jumped straight to full-width kernels, whose
    #: compile-time buffer assignment OOMed HBM at 8M-row caps.
    MAX_DEOPT_RETRIES = 3

    def collect(self) -> ColumnarBatch:
        """Materialize to one batch; the sync boundary where deferred
        fast-path checks resolve.  On FastPathInvalid: disable/escalate
        the offending fast path and re-execute (plans are pure), up to
        MAX_DEOPT_RETRIES times.

        Concurrency: the outermost collect on a thread with no live
        QueryContext creates one (exec/scheduler.py CollectScope) —
        its own conf snapshot, CancelToken, deferred-check registry,
        profile tracer, and an HBM admission slot — so top-level
        collects from different sessions run CONCURRENTLY, each
        isolated; a saturated device queues or sheds new queries at
        admission instead of thrashing the spill/retry lattice."""
        from spark_rapids_tpu.exec import scheduler as S
        from spark_rapids_tpu.utils import checks as CK
        from spark_rapids_tpu.utils import profile as P
        from spark_rapids_tpu.utils import watchdog as W
        if S.current() is None:
            # reset the legacy process-global fallback token so a
            # previous query-less cancellation cannot bleed in
            W.begin_query()
        scope = S.CollectScope(self)
        prof_owner = scope.prof_owner if scope.owns_qc else None
        mark = CK.snapshot()
        prof_error: Optional[BaseException] = None
        try:
            for attempt in range(self.MAX_DEOPT_RETRIES + 1):
                final = attempt == self.MAX_DEOPT_RETRIES
                if attempt:
                    CK.set_retrying(final)
                try:
                    out = self._collect_once()
                    # everything is dispatched; from here the host
                    # waits for the device and reads back: the first
                    # half of the query's `exec:Readback`
                    with P.span(P.SPAN_READBACK) as sp:
                        out, tally = self._drain(out, mark)
                        if sp is not None:
                            sp.args = {
                                "phase": "drain",
                                "rows": out.num_rows
                                if out.num_rows_known else None,
                                "bytes": out.device_size_bytes(),
                                **tally}
                    return out
                except CK.FastPathInvalid as e:
                    if final:
                        prof_error = e
                        raise
                    e.recover_all()
                    P.event(P.EV_DEOPT_RETRY, origin=", ".join(
                        c.origin for c in e.checks))
                    CK.drain_since(mark)  # discard this attempt's rest
                finally:
                    if attempt:
                        CK.set_retrying(False)
        except BaseException as e:
            prof_error = e
            raise
        finally:
            outermost = scope.finish_collect()
            if outermost:
                # only the OUTERMOST collect tears down shared-subtree
                # caches: a nested collect (CpuBroadcastExchange
                # materializing its child mid-plan) must not clear the
                # enclosing query's CommonSubplanExec results
                self.release_execution_state()
                qs = W.query_stats()
                if qs["timeouts"] or qs["cancels"]:
                    # charge watchdog activity to the plan root ONLY on
                    # a tripped query — a clean collect must not force
                    # a metric resolve (device readbacks) it would
                    # otherwise defer
                    self.metrics.add(M.NUM_WATCHDOG_TIMEOUTS,
                                     qs["timeouts"])
                    self.metrics.add(M.NUM_CANCELS, qs["cancels"])
                    self.metrics.add(M.WATCHDOG_DUMPS, qs["dumps"])
                    self.metrics.set_max(
                        M.SLOWEST_HEARTBEAT,
                        qs["slowest_heartbeat_ms"])
                # assemble the QueryProfile LAST so the plan report
                # sees every metric this query charged
                P.end_query(prof_owner, self, error=prof_error)
            # plan lock / admission slot / thread-local context release
            scope.close()

    @staticmethod
    def _drain(out: ColumnarBatch, mark) -> tuple[ColumnarBatch, dict]:
        """The sync boundary of a collect: densify, start the copies to
        the host, resolve the deferred checks.  Returns the batch and
        `CK.verify`'s tally (`checks_given`, `checks_read`)."""
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.utils import checks as CK
        out = out.dense()
        out.prefetch()
        # ONE verify over batch checks + the query's registered checks
        # = one stacked flag readback (a second verify call would pay
        # its own round trip); the two hold the same checks, which
        # verify reads once each.  Under the async pipeline layer the
        # batch's lazy row count rides the SAME readback (host-sync
        # diet: the to_pandas conversion right after this otherwise
        # pays its own round trip for the count).
        checks = list(out.checks) + CK.drain_since(mark)
        tally: dict = {}
        if (not out.num_rows_known
                and C.get_active_conf()[C.PIPELINE_ENABLED]):
            (rows,) = CK.verify(checks, scalars=[out.num_rows_i32],
                                tally=tally)
            out.num_rows = int(rows)
        else:
            CK.verify(checks, tally=tally)
        return out, tally

    def _collect_once(self) -> ColumnarBatch:
        from spark_rapids_tpu.columnar.batch import concat_batches, empty_batch
        from spark_rapids_tpu.exec import scheduler as S
        qc = S.current()
        if qc is not None and qc.collect_depth <= 1:
            # new top-level execution attempt: shared subtrees re-run.
            # Nested collects (broadcast materialization inside a plan)
            # must NOT bump the epoch — that would silently invalidate
            # the outer query's CommonSubplanExec caches mid-execution.
            # Epochs are minted from one process-global counter but
            # scoped to THIS query, so a concurrent query's attempt
            # never invalidates this query's shared-subtree caches.
            qc.epoch = S.new_epoch()
        batches = list(self.execute_columnar())
        if not batches:
            return empty_batch(self.output_schema())
        # a plan that ends in several partitions under a mesh: the
        # answer is one batch on one chip
        from spark_rapids_tpu.parallel import mesh as PM
        batches = PM.to_one_chip(batches, "collect")
        # sparse_ok: collect() densifies right after, so the concat can
        # skip per-input compaction gathers — one gather round total
        return concat_batches(batches, sparse_ok=True)

    def to_pandas(self):
        return self.collect().to_pandas()

    def update_output_metrics(self, batch: ColumnarBatch) -> None:
        self.metrics.add(M.NUM_OUTPUT_ROWS, batch._rows)
        self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)

    def oom_retry_batches(self, batch: ColumnarBatch, body,
                          split: bool = True, out_bytes_fn=None,
                          label: str = None):
        """Reservation-aware batch processing: route one batch's
        materialization through the OOM retry harness (memory/retry.py)
        — reserve HBM for the output, spill under pressure with the
        semaphore yielded, split the input in half and retry on
        reservation failure, and past the row floor degrade via the
        conf'd fallback.  Yields one `body(piece)` result per (possibly
        split) piece in row order, charging this exec's numRetries /
        numSplitRetries / spillBytes / retryBlockTime metrics.

        `split=False` is for single-batch contracts that cannot
        subdivide their input (window frames, RequireSingleBatch
        consumers): pressure there spills + retries in place and the
        floor fallback handles the rest."""
        from spark_rapids_tpu.memory import retry as R
        from spark_rapids_tpu.utils import watchdog as W
        label = label or self.name()
        # batch boundary = cancellation point: a watchdog-cancelled
        # query stops dispatching new work here instead of grinding on
        W.check_cancelled()
        if split:
            for out in R.with_split_retry(
                    batch, body, metrics=self.metrics,
                    out_bytes_fn=out_bytes_fn, label=label):
                W.check_cancelled()
                yield out
        else:
            nbytes = (out_bytes_fn or R.estimate_batch_bytes)(batch)
            yield R.with_retry(lambda: body(batch), out_bytes=nbytes,
                               metrics=self.metrics, label=label)

    def name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + self.describe()
        for c in self._children:
            s += "\n" + c.tree_string(indent + 1)
        return s

    def release_execution_state(self) -> None:
        """Drop per-execution materialized state (CommonSubplanExec
        caches) after a collect completes, so a finished query doesn't
        pin its shared subtrees' device batches."""
        for c in self._children:
            c.release_execution_state()

    def describe(self) -> str:
        return self.name()

    def __repr__(self):
        return self.tree_string()


#: THREAD MODEL (superseding the ADVICE r4 one-query-at-a-time note):
#: execution-attempt epochs, collect nesting depth, the CancelToken,
#: the deferred-check registry, and the profile tracer all live on a
#: per-query QueryContext (exec/scheduler.py) installed by the
#: outermost collect and threaded to helper threads via TaskContext —
#: so top-level collects from DIFFERENT sessions run concurrently,
#: each against its own conf snapshot, serialized only when they share
#: one plan INSTANCE (the per-plan `_plan_lock`).  Epochs are minted
#: from one process-global counter (scheduler.new_epoch) so no two
#: attempts, in any query, can collide on a CommonSubplanExec cache
#: tag.


class CommonSubplanExec(TpuExec):
    """Execute-once wrapper for a subtree shared by several parents
    (plan DAGs with reused CTEs: TPC-DS q64's cross_sales, q23's
    frequent-items subquery).  The role Spark's ReusedExchangeExec
    plays for the reference: without it every consumer re-executes the
    whole shared subtree."""

    def __init__(self, child: TpuExec):
        super().__init__(child)
        self._epoch = -1
        self._cached = None

    def output_schema(self):
        return self.child.output_schema()

    def output_partition_count(self):
        return self.child.output_partition_count()

    @property
    def coalesce_after(self) -> bool:
        # transparent for coalesce insertion: a shared subtree rooted
        # at a batch-shrinking exec still wants coalesce above it
        return self.child.coalesce_after

    def describe(self):
        return "CommonSubplanExec"

    def execute_partitions(self):
        from spark_rapids_tpu.exec import scheduler as S
        epoch = S.current_epoch()
        if self._epoch != epoch:
            self._cached = [list(it)
                            for it in self.child.execute_partitions()]
            self._epoch = epoch
        return [iter(p) for p in self._cached]

    def execute_columnar(self):
        for it in self.execute_partitions():
            yield from it

    def release_execution_state(self):
        self._cached = None
        self._epoch = -1
        super().release_execution_state()


class SchemaOnlyExec(TpuExec):
    """Placeholder child carrying just a schema, for internal helper
    execs (merge nodes, shared sorters)."""

    def __init__(self, schema: T.Schema):
        super().__init__()
        self._schema = schema

    def output_schema(self) -> T.Schema:
        return self._schema


class LeafExec(TpuExec):
    def execute_partitions(self):
        from spark_rapids_tpu.utils import profile as P
        return [P.wrap_operator(self, 0, self.execute_columnar())]


class UnaryExecBase(TpuExec):
    """Partition-local single-child operator: processes one child batch
    iterator into an output iterator."""

    def process_partition(self, batches: Iterator[ColumnarBatch]
                          ) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        # preserve partition-local semantics (RDD mapPartitions): process
        # each child partition separately, then chain
        for it in self.execute_partitions():
            yield from it

    def execute_partitions(self):
        from spark_rapids_tpu.utils import profile as P
        return [P.wrap_operator(self, i, self.process_partition(it))
                for i, it in enumerate(self.child.execute_partitions())]


def bind_exprs(exprs: Sequence[Expression], schema: T.Schema
               ) -> list[Expression]:
    return [e.bind(schema) for e in exprs]
