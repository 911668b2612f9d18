"""Sort operator (reference `GpuSortExec.scala:50-124`).

Local (per-partition) sort runs per batch; global sort requires its child
coalesced to a single batch (RequireSingleBatch goal), same contract as the
reference.  The whole sort — key encode, lexsort, gather of every column —
is one jitted kernel per batch bucket.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import (
    CoalesceGoal, RequireSingleBatch, TpuExec, UnaryExecBase,
    batch_signature, make_eval_context, named_jit)
from spark_rapids_tpu.exprs.base import Expression
from spark_rapids_tpu.ops.sort_encode import multi_key_argsort
from spark_rapids_tpu.utils import metrics as M


@dataclasses.dataclass(frozen=True)
class SortOrder:
    """Spark SortOrder: expression + direction + null ordering.  Defaults
    follow Spark: ascending -> nulls first, descending -> nulls last."""
    expr: Expression
    ascending: bool = True
    nulls_first: Optional[bool] = None

    @property
    def resolved_nulls_first(self) -> bool:
        if self.nulls_first is None:
            return self.ascending
        return self.nulls_first


def asc(e: Expression) -> SortOrder:
    return SortOrder(e, True)


def desc(e: Expression) -> SortOrder:
    return SortOrder(e, False)


class SortExec(UnaryExecBase):
    def __init__(self, order: Sequence[SortOrder], child: TpuExec,
                 global_sort: bool = True):
        super().__init__(child)
        self.order = list(order)
        self.global_sort = global_sort
        self._schema = child.output_schema()
        self._bound = [o.expr.bind(self._schema) for o in self.order]

    def output_schema(self) -> T.Schema:
        return self._schema

    def children_coalesce_goal(self) -> list[Optional[CoalesceGoal]]:
        return [RequireSingleBatch() if self.global_sort else None]

    def describe(self):
        dirs = ",".join(
            f"{o.expr!r} {'ASC' if o.ascending else 'DESC'}"
            for o in self.order)
        return f"SortExec({dirs}, global={self.global_sort})"

    def cache_scope(self):
        from spark_rapids_tpu.exprs.base import fingerprint
        return (fingerprint(self._bound),
                tuple((o.ascending, o.resolved_nulls_first)
                      for o in self.order))

    def _kernel(self, batch: ColumnarBatch, head: Optional[int] = None):
        key = ("sort", head, batch_signature(batch))
        label = "sort" if head is None else "sort-head"

        def build():
            bound = self._bound
            specs = [(o.ascending, o.resolved_nulls_first)
                     for o in self.order]
            cap = batch.capacity
            out_cap = cap
            if head is not None and head < cap:
                from spark_rapids_tpu.columnar.vector import bucket_capacity
                out_cap = bucket_capacity(head)

            @named_jit(label)
            def kernel(columns, num_rows, mask=None):
                ctx = make_eval_context(columns, cap, num_rows, mask)
                keys = [e.eval(ctx) for e in bound]
                perm = multi_key_argsort(
                    [(k, a, nf) for k, (a, nf) in zip(keys, specs)],
                    ctx.row_mask)
                # selected rows sort FIRST (row_mask is the most
                # significant key), so a sparse input compacts for free
                count = num_rows
                if out_cap < cap:
                    # fused limit: gather only the head — skipping the
                    # full-capacity payload gathers is the whole win
                    # (each costs ~30ms at 4M rows on this chip)
                    perm = perm[:out_cap]
                    count = jnp.minimum(num_rows,
                                        jnp.int32(min(head, out_cap)))
                valid = jnp.arange(out_cap) < count
                return [c.gather(perm, valid) for c in columns]

            return kernel

        return self.kernels.get_or_build(
            key, build, meta=self.kp_meta(label))

    def output_partition_count(self) -> int:
        if not self.global_sort:
            return self.child.output_partition_count()
        return 1

    def execute_partitions(self):
        if not self.global_sort:
            return [self.process_partition(it)
                    for it in self.child.execute_partitions()]

        # a global sort is a single output partition over ALL child
        # partitions (the distributed planner replaces this with a range
        # exchange; standalone we collapse here)
        def chain():
            for it in self.child.execute_partitions():
                yield from it
        return [self.process_partition(chain())]

    def process_partition(self, batches,
                          head: Optional[int] = None
                          ) -> Iterator[ColumnarBatch]:
        if self.global_sort:
            yield from self._global_sort(batches, head)
            return
        for batch in batches:
            out = self._sort_with_retry(batch, head)
            self.update_output_metrics(out)
            yield out

    def _global_sort(self, batches,
                     head: Optional[int]) -> Iterator[ColumnarBatch]:
        """Global-sort lane with out-of-core degradation: stream the
        child, and while the buffered working set fits the HBM window
        keep the existing coalesce-to-one-batch path; once the
        accounted estimate says it cannot fit (memory/oocore.py
        `should_go_external`), switch to an external merge sort —
        sorted runs spill through the host→disk tiers and k-way merge
        back in window-sized groups, instead of split-retrying the
        single giant batch down to the row floor and erroring."""
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.memory import oocore as OC
        from spark_rapids_tpu.memory import retry as R
        from spark_rapids_tpu.utils import profile as P
        conf = C.get_active_conf()
        pending: list[ColumnarBatch] = []
        pending_bytes = 0
        runs: list = []
        external = False
        # runs flush at window/fan-in so a merge group of MERGE_FAN_IN
        # runs fits back inside the window
        run_target = max(1, OC.window_bytes(conf) // OC.MERGE_FAN_IN)

        def flush_run():
            nonlocal pending, pending_bytes
            if not pending:
                return
            from spark_rapids_tpu.columnar.batch import concat_batches
            merged = (concat_batches([p.dense() for p in pending])
                      if len(pending) > 1 else pending[0])
            # head pruning per run is sound for top-N: each run's head
            # is a superset of its contribution to the global head
            sorted_b = self._sort_with_retry(merged, head)
            runs.append(OC.spill_run(sorted_b, label=self.name(),
                                     metrics=self.metrics, conf=conf))
            pending = []
            pending_bytes = 0

        for batch in batches:
            pending.append(batch)
            pending_bytes += R.estimate_batch_bytes(batch)
            if not external and OC.should_go_external(pending_bytes, conf):
                external = True
                P.event(P.EV_OOCORE_DEGRADE, op=self.name(),
                        est_bytes=pending_bytes, algo="external-sort")
            if external and pending_bytes > run_target:
                flush_run()

        if not external:
            # working set fit: the original coalesce + one-shot sort
            from spark_rapids_tpu.exec.coalesce import coalesce_iterator
            for batch in coalesce_iterator(
                    iter(pending), RequireSingleBatch(), self._schema,
                    self.metrics):
                out = self._sort_with_retry(batch, head)
                self.update_output_metrics(out)
                yield out
            return

        flush_run()
        out = self._merge_spilled_runs(runs, head, conf)
        self.update_output_metrics(out)
        yield out

    def _merge_spilled_runs(self, runs: list, head: Optional[int],
                            conf) -> ColumnarBatch:
        """Hierarchical merge of spilled sorted runs: each pass reads
        back window-sized groups, merges each with one in-window sort
        (the OOM split-retry lattice stays active inside), and
        re-spills until one run remains.  Bounded by
        `oocore.maxRecursionDepth` passes — past it, a descriptive
        error, never a hang or partial data."""
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.columnar.batch import concat_batches
        from spark_rapids_tpu.memory import oocore as OC
        from spark_rapids_tpu.memory.retry import TpuOutOfCoreError
        from spark_rapids_tpu.utils import profile as P
        from spark_rapids_tpu.utils import watchdog as W
        window = OC.window_bytes(conf)
        max_passes = max(1, int(conf[C.OOCORE_MAX_RECURSION]))
        passes = 0
        with W.heartbeat(f"{self.name()}.oocore-merge", kind="task",
                         conf=conf) as hb:
            while len(runs) > 1:
                if passes >= max_passes:
                    raise TpuOutOfCoreError(
                        f"{self.name()}: external sort still has "
                        f"{len(runs)} runs after {passes} merge passes "
                        f"(spark.rapids.memory.oocore.maxRecursionDepth"
                        f"={max_passes}) — the merge window "
                        f"({window} bytes) is too small for the run "
                        f"count; raise the HBM budget or "
                        f"oocore.windowFraction")
                passes += 1
                self.metrics.add(M.NUM_EXTERNAL_MERGE_PASSES, 1)
                P.event(P.EV_OOCORE_MERGE_PASS, op=self.name(),
                        num_runs=len(runs))
                next_runs = []
                pending_groups: list[list] = [[]]
                group_bytes = 0
                for r in runs:
                    # 2x: serialized payload + sort scratch must both
                    # fit the window.  A group always takes at least 2
                    # runs (progress guarantee — every pass at least
                    # halves the run count; the inner split-retry
                    # lattice absorbs any window overshoot)
                    if (len(pending_groups[-1]) >= 2
                            and group_bytes + 2 * r.nbytes > window):
                        pending_groups.append([])
                        group_bytes = 0
                    pending_groups[-1].append(r)
                    group_bytes += 2 * r.nbytes
                for group in pending_groups:
                    W.maybe_hang("oocore-merge", conf)
                    merged = concat_batches(
                        [r.read(self.metrics).dense() for r in group])
                    sorted_b = self._sort_with_retry(merged, head)
                    for r in group:
                        r.free()
                    hb.beat()
                    if len(pending_groups) == 1:
                        return sorted_b  # final merge: no re-spill
                    next_runs.append(OC.spill_run(
                        sorted_b, label=self.name(),
                        metrics=self.metrics, conf=conf))
                runs = next_runs
        final = runs[0]
        batch = final.read(self.metrics)
        final.free()
        return batch

    def _sort_one_batch(self, batch: ColumnarBatch,
                        head: Optional[int]) -> ColumnarBatch:
        with self.metrics.timed(M.TOTAL_TIME):
            kernel = self._kernel(batch, head)
            if batch.sparse is not None:
                cols = kernel(batch.columns, batch.num_rows_i32,
                              batch.sparse)
            else:
                cols = kernel(batch.columns, batch.num_rows_i32)
            rows = batch._rows
            if head is not None:
                rows = (min(rows, head) if batch.num_rows_known
                        else jnp.minimum(batch.num_rows_i32,
                                         jnp.int32(head)))
            return ColumnarBatch(self._schema, list(cols), rows,
                                 batch.checks)

    def _sort_with_retry(self, batch: ColumnarBatch,
                         head: Optional[int]) -> ColumnarBatch:
        """Materialization point routed through the OOM harness: under
        reservation failure the input halves, each half sorts at half
        capacity (a fused `head` keeps only each half's head — sound
        for top-N), and the sorted runs merge through ONE final
        no-split sort pass over their concatenation.  Key VALUES are
        bit-exact vs the unsplit sort; only the order within equal
        keys can differ (Spark does not promise sort stability)."""
        pieces = list(self.oom_retry_batches(
            batch, lambda b: self._sort_one_batch(b, head),
            label=f"{self.name()}.sortBatch"))
        if len(pieces) == 1:
            return pieces[0]
        from spark_rapids_tpu.columnar.batch import concat_batches
        merged = concat_batches([p.dense() for p in pieces])
        (out,) = tuple(self.oom_retry_batches(
            merged, lambda b: self._sort_one_batch(b, head),
            split=False, label=f"{self.name()}.mergeRuns"))
        return out

    def execute_head(self, n: int) -> Iterator[ColumnarBatch]:
        """Global sort fused with a LIMIT n: the sort kernel gathers only
        the head rows at bucket(n) capacity (a GlobalLimitExec parent
        dispatches here; Spark's planner does the same fusion by
        rewriting to TakeOrderedAndProject)."""
        def chain():
            for it in self.child.execute_partitions():
                yield from it
        return self.process_partition(chain(), head=n)


class SortedTopNExec(UnaryExecBase):
    """TakeOrderedAndProject analog: per-batch top-N keep + final merge.
    (Reference uses CPU fallback for TakeOrderedAndProject at this
    snapshot; we accelerate it since sort is cheap on device.)"""

    def __init__(self, n: int, order: Sequence[SortOrder], child: TpuExec):
        super().__init__(child)
        self.n = n
        self.order = list(order)
        self._schema = child.output_schema()
        # one shared sorter so per-batch sort kernels hit ONE compile cache
        from spark_rapids_tpu.exec.base import SchemaOnlyExec
        self._sorter = SortExec(self.order, SchemaOnlyExec(self._schema),
                                global_sort=False)

    def output_schema(self):
        return self._schema

    def _sort_one(self, batch: ColumnarBatch) -> ColumnarBatch:
        kern = self._sorter._kernel(batch)
        if batch.sparse is not None:
            cols = kern(batch.columns, batch.num_rows_i32, batch.sparse)
        else:
            cols = kern(batch.columns, batch.num_rows_i32)
        return ColumnarBatch(self._schema, list(cols), batch._rows,
                             batch.checks)

    def _topk_applicable(self) -> bool:
        if len(self.order) != 1 or self.n > 128:
            return False
        dt = self._sorter._bound[0].data_type(self._schema)
        return not dt.is_string

    def _prune_one(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Per-batch candidate pruning.  Single numeric key: lax.top_k
        over an exact sentinel-encoded score (~10x cheaper than the full
        bitonic sort at multi-M rows); NaN/inf/past-2^53 magnitudes
        route to the sort branch via lax.cond so ordering stays exact.
        The cross-batch merge re-sorts candidates exactly, fixing any
        top_k tie order."""
        if not self._topk_applicable():
            return self._sort_one(batch).take_head(self.n)
        kern = self.kernels.get_or_build(
            ("topn-k", self.n, batch_signature(batch)),
            lambda: named_jit("topn-k", self._build_topk(batch.capacity)),
            meta=self.kp_meta("topn-k"))
        if batch.sparse is not None:
            cols, count = kern(batch.columns, batch.num_rows_i32,
                               batch.sparse)
        else:
            cols, count = kern(batch.columns, batch.num_rows_i32)
        return ColumnarBatch(self._schema, list(cols), count,
                             batch.checks)

    def _build_topk(self, cap: int):
        from spark_rapids_tpu.columnar.vector import bucket_capacity
        o = self.order[0]
        bound = self._sorter._bound[0]
        dt = bound.data_type(self._schema)
        kk = min(self.n, cap)
        out_cap = bucket_capacity(kk)
        BIG, NBIG = 4e300, 2e300

        def kernel(columns, num_rows, mask=None):
            ctx = make_eval_context(columns, cap, num_rows, mask)
            k = bound.eval(ctx)
            d = k.data.astype(jnp.float64)
            valid = k.validity & ctx.row_mask
            if dt.is_floating:
                special = jnp.any(valid & (jnp.isnan(d) |
                                           (jnp.abs(d) >= 1e290)))
            else:
                special = jnp.any(valid &
                                  (jnp.abs(d) >= jnp.float64(2**53)))

            sv = d if not o.ascending else -d
            if dt.is_floating:
                nan_score = NBIG if not o.ascending else -NBIG
                sv = jnp.where(jnp.isnan(d), nan_score, sv)
            null_score = BIG if o.resolved_nulls_first else -BIG
            score = jnp.where(k.validity, sv, null_score)
            score = jnp.where(ctx.row_mask, score, -jnp.inf)

            # 64-bit top_k is ~8x slower than 32-bit on this chip: prune
            # candidates with a MONOTONE f32 downcast of the score, then
            # re-rank just the candidates exactly in f64.  Sound unless
            # the f32 tie bucket at the candidate boundary could hide a
            # true top-k row — detected on device and routed (with the
            # NaN/magnitude specials) to the exact 64-bit sort branch.
            kkp = min(cap, max(4 * kk, kk + 118))
            # clip BEFORE the downcast so the +/-BIG null sentinels stay
            # FINITE in f32 (a raw downcast overflows them to +/-inf,
            # conflating nulls-last rows with row-mask-excluded rows);
            # masked rows are re-pinned to -inf afterwards.  clip is
            # monotone non-strict, so collapsed extremes are exactly the
            # tie case the boundary guard already routes to the exact
            # branch.
            score32 = jnp.where(
                ctx.row_mask,
                jnp.clip(score, -3.0e38, 3.0e38).astype(jnp.float32),
                -jnp.inf)
            vals32, cand = jax.lax.top_k(score32, kkp)
            cand_exact = jnp.take(score, cand)
            order = jnp.argsort(-cand_exact)
            topk_idx = jnp.take(cand, order[:kk]).astype(jnp.int32)
            # boundary tie: the K'-th kept f32 key equals the k-th —
            # rows beyond K' with the same f32 key may beat kept ones
            # in f64.  A -inf boundary means fewer than k real rows, so
            # every real row is already a candidate; kkp == cap means
            # EVERY row is a candidate (statically sound).
            if kkp >= cap:
                unsound = jnp.bool_(False)
            else:
                unsound = ((vals32[kkp - 1] == vals32[kk - 1])
                           & (vals32[kk - 1] != -jnp.inf))

            def sort_branch():
                perm = multi_key_argsort(
                    [(k, o.ascending, o.resolved_nulls_first)],
                    ctx.row_mask)
                return perm[:kk].astype(jnp.int32)

            idx = jax.lax.cond(special | unsound, sort_branch,
                               lambda: topk_idx)
            count = jnp.minimum(jnp.asarray(num_rows, jnp.int32), kk)
            pad_idx = jnp.zeros(out_cap, jnp.int32).at[:kk].set(idx)
            valid_out = jnp.arange(out_cap) < count
            cols = [c.gather(pad_idx, valid_out) for c in columns]
            return cols, count
        return kernel

    def execute_columnar(self):
        from spark_rapids_tpu.columnar.batch import concat_batches
        from spark_rapids_tpu.exec.pipeline import drain_partitions
        from spark_rapids_tpu.parallel import mesh as PM

        def pruned_of(part):
            for batch in part:
                top = self._prune_one(batch)
                if top.maybe_nonempty():
                    yield top
        # per partition (under a mesh: a chip each, side by side), then
        # ONE single-partition merge of n candidates a partition
        pruned = [top for tops in drain_partitions(
            [pruned_of(part) for part in self.child.execute_partitions()],
            label="topn-prune", metrics=self.metrics) for top in tops]
        if not pruned:
            return
        merged = concat_batches(PM.to_one_chip(pruned, "topn-merge"))
        final = self._sort_one(merged).take_head(self.n)
        self.update_output_metrics(final)
        yield final

    def execute_partitions(self):
        return [self.execute_columnar()]

