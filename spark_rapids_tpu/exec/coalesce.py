"""Batch coalescing (reference `GpuCoalesceBatches.scala`): concatenate
small batches up to a CoalesceGoal — TargetSize(bytes) or
RequireSingleBatch.  On TPU this additionally *re-buckets* capacity, which
is what keeps the kernel compile cache small after filters shrink batches.
"""
from __future__ import annotations

from typing import Iterator, Optional

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, concat_batches, rows_made_known)
from spark_rapids_tpu.columnar.vector import bucket_capacity
from spark_rapids_tpu.exec.base import (
    CoalesceGoal, RequireSingleBatch, TargetSize, TpuExec, UnaryExecBase)
from spark_rapids_tpu.utils import metrics as M


#: a lazy (deferred-selection) batch passes through coalesce un-sliced
#: while its capacity is within this multiple of the row cap — bounded
#: so row-exploding join/expand outputs still slice (their downstream
#: compile cost is what the split pipeline contains)
LAZY_PASS_MULT = 8


def coalesce_iterator(batches: Iterator[ColumnarBatch],
                      goal: CoalesceGoal,
                      schema: T.Schema,
                      metrics,
                      max_rows: int = None) -> Iterator[ColumnarBatch]:
    """The AbstractGpuCoalesceIterator analog.  `max_rows` (resolved by
    the caller at plan time — the draining thread may not carry the
    session conf) caps emitted batch row counts for TargetSize goals.

    Pass-through EXCEPTION to the row cap: a LAZY batch (row count
    still a device scalar) whose capacity is within `LAZY_PASS_MULT` x
    `max_rows` is emitted WHOLE — uncounted and un-sliced — because its
    memory is already allocated (slicing duplicates, not frees) and the
    count sync (a blocking device round trip; its cost is not measured
    on the current machine) would dominate post-filter
    pipelines.  Consumers that size work by rows must therefore treat
    batch CAPACITY as the bound for lazy batches; the exchange's
    oversized-batch shard guard (shuffle/exchange.py) does exactly
    that so an up-to-8x lazy batch cannot land whole on one chip."""
    if max_rows is None:
        from spark_rapids_tpu import config as C
        max_rows = C.get_active_conf()[C.MAX_BATCH_ROWS]
    if isinstance(goal, RequireSingleBatch):
        got = [b for b in batches if b.maybe_nonempty()]
        if not got:
            from spark_rapids_tpu.columnar.batch import empty_batch
            yield empty_batch(schema)
            return
        if len(got) > 1:
            # a barrier (a join's build side under AQE, a global sort, a
            # window): its consumer's kernels run at this batch's
            # capacity, so past one batch of padding the inputs' counts
            # come to the host in one read and the concat is tight —
            # the rule of `HashJoinExec._concat_build`
            rows_made_known(got, "coalesce.single",
                            beyond=bucket_capacity(int(max_rows)))
            got = [b for b in got if b.maybe_nonempty()] or got[:1]
        out = concat_batches(got) if len(got) > 1 else _rebucket(got[0])
        metrics.add(M.NUM_OUTPUT_BATCHES, 1)
        metrics.add(M.NUM_OUTPUT_ROWS, out._rows)
        yield out
        return

    target = goal.bytes if isinstance(goal, TargetSize) else 1 << 31
    pending: list[ColumnarBatch] = []
    pending_bytes = 0
    pending_rows = 0
    for big in batches:
        metrics.add(M.NUM_INPUT_BATCHES, 1)
        metrics.add(M.NUM_INPUT_ROWS, big._rows)
        if not big.maybe_nonempty():
            continue
        # row cap keeps capacities inside the bounded bucket set so
        # downstream kernels reuse compiled shapes; oversized batches
        # (row-expanding joins/expand) are sliced, not forwarded
        # lazy slicing: materializing every slice up front would hold a
        # second full copy of an oversized batch on device at once
        # lazy batches are sized by CAPACITY (a safe upper bound on
        # rows) so accumulation stays sync-free.  A lazy batch whose
        # capacity moderately exceeds the row cap passes through WHOLE:
        # its memory is already allocated (slicing duplicates, not
        # frees), every exec consumes deferred-selection batches, and
        # the sync (a blocking device round trip) + two gather rounds
        # per batch would otherwise be paid by post-filter pipelines
        # (13 syncs in q27; times not measured on the current machine).  Only a cap past LAZY_PASS_MULT x the row cap —
        # the row-exploding join/expand shapes whose downstream compile
        # cost the bounded split pipeline exists to contain — pays the
        # count sync and slices.
        lazy_bounded = (not big.num_rows_known and
                        big.capacity <= LAZY_PASS_MULT * max_rows)
        # reading num_rows on a lazy batch is a count SYNC — only the
        # must-slice shape (lazy + cap past the pass-through bound) pays
        # it; per-piece accounting below recomputes its own size
        big_rows = big.num_rows if not lazy_bounded else None
        if lazy_bounded or big_rows <= max_rows:
            pieces = (big,)
        else:
            # densify ONCE before slicing: ColumnarBatch.slice on a
            # sparse batch would re-run the full-capacity compaction
            # gather per slice
            dense_big = big.dense()
            pieces = (dense_big.slice(lo, min(max_rows,
                                              dense_big.num_rows - lo))
                      for lo in range(0, dense_big.num_rows, max_rows))
        for b in pieces:
            b_rows = (b.num_rows if b.num_rows_known else b.capacity)
            est = _row_bytes(b) * b_rows
            if pending and (pending_bytes + est > target or
                            pending_rows + b_rows > max_rows):
                yield _emit(pending, metrics)
                pending, pending_bytes, pending_rows = [], 0, 0
            pending.append(b)
            pending_bytes += est
            pending_rows += b_rows
    if pending:
        yield _emit(pending, metrics)


def _row_bytes(b: ColumnarBatch) -> int:
    total = 0
    for f, c in zip(b.schema.fields, b.columns):
        if f.dtype.is_string:
            total += c.char_cap + 5
        else:
            total += f.dtype.storage_dtype.itemsize + 1
    return max(total, 1)


def _rebucket(b: ColumnarBatch) -> ColumnarBatch:
    """Shrink an over-padded batch into its tight bucket (e.g. after a
    selective filter) so downstream kernels compile for a smaller shape."""
    if not b.num_rows_known:
        # tightening needs the count: a blocking read (0.4 ms on an idle
        # v5e, PERF.md PR 30; on a busy one it waits for the queue)
        return b
    tight = bucket_capacity(b.num_rows)
    if tight < b.capacity:
        return b.with_capacity(tight)
    return b


def _emit(pending: list[ColumnarBatch], metrics) -> ColumnarBatch:
    # sparse_ok: the single-batch pass-through path already hands
    # deferred-selection batches to the same downstream consumers, so
    # the merged batch may stay sparse too (no per-input dense gathers)
    out = concat_batches(pending, sparse_ok=True) if len(pending) > 1 \
        else _rebucket(pending[0])
    metrics.add(M.NUM_OUTPUT_BATCHES, 1)
    metrics.add(M.NUM_OUTPUT_ROWS, out._rows)
    return out


class CoalesceBatchesExec(UnaryExecBase):
    """Reference GpuCoalesceBatches exec node, inserted by the transition
    pass per each operator's childrenCoalesceGoal."""

    def __init__(self, goal: CoalesceGoal, child: TpuExec,
                 max_rows: "Optional[int]" = None):
        super().__init__(child)
        self.goal = goal
        from spark_rapids_tpu import config as C
        # the session conf's cap is passed by the transition pass;
        # resolved at plan time because the draining thread may not
        # carry the conf
        self._max_rows = (max_rows if max_rows is not None
                          else C.get_active_conf()[C.MAX_BATCH_ROWS])

    def output_schema(self):
        return self.child.output_schema()

    def describe(self):
        return f"CoalesceBatchesExec({self.goal})"

    def process_partition(self, batches):
        # coalesce is a pipeline break: its producer side (the child's
        # batches + the concat/re-bucket dispatches) runs ahead on a
        # prefetch thread while the downstream consumer computes.  The
        # conf is resolved HERE (execution time, inside collect()'s
        # session) — never at plan build, where the session conf is not
        # installed and a captured default would leak to the producer
        # thread and flip conf-gated kernel lanes (observed as q15's
        # f32-vs-f64 aggregation mismatch).
        from spark_rapids_tpu.exec.pipeline import maybe_prefetch
        return maybe_prefetch(
            coalesce_iterator(batches, self.goal, self.output_schema(),
                              self.metrics, max_rows=self._max_rows),
            label="coalesce", metrics=self.metrics)
