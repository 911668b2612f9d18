"""Hash aggregation (reference `aggregate.scala:312` GpuHashAggregateExec).

The reference runs cuDF groupby per batch, then concatenates partial
results and re-merges until one batch remains.  The TPU version keeps the
same two-phase shape with sort-based segments:

  per input batch : sort rows by group keys -> segment ids -> update aggs
  on exhaustion   : concat partials -> sort -> merge aggs -> evaluate

Modes mirror Spark: Partial (update only, emits keys+intermediates),
Final (merge intermediates, evaluate), Complete (update+evaluate in one
node — used for single-stage local plans).  The reduction path (no group
keys) skips the sort entirely and uses masked whole-batch reductions.

The grouped kernel (`_groupby_kernel`: `jit_agg_update` / `jit_agg_merge`)
holds two bodies under one `lax.cond`, chosen on the device from the
batch alone.  `elect_group_leaders` spends up to `FEW_GROUPS_MAX` rounds
of exact key comparison naming the batch's groups; a batch they cover
takes `_few_groups` (no sort, no gather, no scan: each measure reduced
once a group under the group's mask, in its own dtype), any other
`_sorted_groups`, today's sort body, after the rounds it lost (one pass
over the key columns each).  The cond is built when every function
reduces through the kernel's context (`reduces_through_scans`) and the
banded lane is not taken, in both phases; it has no switch, keeps no
state, and registers no check (membership is never a hash's word).
Which body ran comes back as a device scalar and is counted lazily
(`numFewGroupBatches` of `numFewGroupsOffered`).
"""
from __future__ import annotations

import dataclasses
import enum
import logging
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, concat_batches, programs_of, rows_made_known)
from spark_rapids_tpu.columnar.vector import (MIN_CAPACITY, ColumnVector,
                                              bucket_capacity)
from spark_rapids_tpu.exec.base import (
    SchemaOnlyExec as _SchemaOnly, TpuExec, UnaryExecBase,
    batch_signature, make_eval_context, named_jit)
from spark_rapids_tpu.exprs.aggregates import (
    AggAlias, AggContext, AggregateFunction, run_agg_phase)
from spark_rapids_tpu.exprs.base import Expression, output_name
from spark_rapids_tpu.ops.sort_encode import (elect_group_leaders,
                                              hash_sort_bounds,
                                              sort_with_bounds,
                                              wide_key_set)
from spark_rapids_tpu.utils import checks as CK
from spark_rapids_tpu.utils import metrics as M

log = logging.getLogger("spark_rapids_tpu.aggregate")


class AggMode(enum.Enum):
    PARTIAL = "partial"
    FINAL = "final"
    COMPLETE = "complete"


def _to_alias(a, i: int) -> AggAlias:
    if isinstance(a, AggAlias):
        return a
    return AggAlias(a, f"agg{i}")


class HashAggregateExec(UnaryExecBase):
    def __init__(self, group_exprs: Sequence[Expression],
                 aggregates: Sequence,
                 child: TpuExec,
                 mode: AggMode = AggMode.COMPLETE,
                 pre_stage=None):
        super().__init__(child)
        self.mode = mode
        self.group_exprs = list(group_exprs)
        self.aggregates = [_to_alias(a, i) for i, a in enumerate(aggregates)]
        #: whole-stage fusion (plan/fusion.py ComposedStage): a fused
        #: project/filter chain evaluated INSIDE every update-lane
        #: kernel before grouping — group/input expressions bind
        #: against the stage's output schema while batches arrive in
        #: the raw child schema.  Update/complete phases only (a FINAL
        #: merge reads positional intermediates, never raw inputs).
        self._pre_stage = pre_stage
        self._fused_event_done = False
        if pre_stage is not None:
            assert mode != AggMode.FINAL, \
                "pre_stage fusion applies to update lanes only"
        child_schema = (pre_stage.schema if pre_stage is not None
                        else child.output_schema())
        self._child_schema = child_schema
        self._bound_groups = [e.bind(child_schema) for e in self.group_exprs]
        self._group_fields = tuple(
            T.Field(output_name(e, i), b.data_type(child_schema))
            for i, (e, b) in enumerate(
                zip(self.group_exprs, self._bound_groups)))

        self._funcs = [a.func for a in self.aggregates]
        self._inter_offsets = []
        if mode == AggMode.FINAL:
            # child emits keys + intermediates; resolve types positionally
            # (original input columns are gone from the partial schema)
            off = len(self._group_fields)
            self._inter_types = []
            for f in self._funcs:
                n = f.num_intermediates
                self._inter_offsets.append((off, off + n))
                self._inter_types.append(tuple(
                    child_schema.fields[i].dtype for i in range(off, off + n)))
                off += n
        else:
            self._bound_inputs = [
                [e.bind(child_schema) for e in f.input_exprs()]
                for f in self._funcs]
            self._inter_types = [
                tuple(f.intermediate_types(child_schema))
                for f in self._funcs]
            off = len(self._group_fields)
            for ts in self._inter_types:
                self._inter_offsets.append((off, off + len(ts)))
                off += len(ts)

        # output schema
        fields = list(self._group_fields)
        if mode == AggMode.PARTIAL:
            for a, ts in zip(self.aggregates, self._inter_types):
                for j, it in enumerate(ts):
                    fields.append(T.Field(f"{a.name}#{j}", it))
        elif mode == AggMode.FINAL:
            for a, ts in zip(self.aggregates, self._inter_types):
                fields.append(
                    T.Field(a.name, a.func.result_from_intermediates(ts)))
        else:
            for a in self.aggregates:
                fields.append(
                    T.Field(a.name, a.func.result_type(child_schema)))
        self._schema = T.Schema(tuple(fields))
        # static qualification for the dictionary fast path, computed
        # once (None = never applicable for this exec)
        self._dict_qual = self._dict_plan()
        self._dict_range_misses = 0
        # banded windowed-MXU lane: every aggregate must be expressible
        # as per-group f32 sums (keys are unrestricted — reps travel as
        # first-row-index limbs)
        self._banded_qual = all(
            type(f).__name__ in ("Sum", "Count", "Average")
            for f in self._funcs)
        # padded dictionary width (int for a single key; tuple of
        # per-key pads for the composite multi-key path), sized from a
        # one-time first-batch range probe (None until probed)
        self._dict_gpad: Optional[object] = None
        #: the lane the last batch took: "dict", "banded", "reduce",
        #: "sort-segment" (the grouped kernel with its sort body alone)
        #: or "few-or-sort" (the same with the few-groups body built
        #: in: WHICH body a batch took is decided on the device and
        #: counted in `numFewGroupBatches`); host-known, the group-by
        #: spans' `lane`
        self._lane: Optional[str] = None

    def output_schema(self) -> T.Schema:
        return self._schema

    def describe(self):
        keys = ", ".join(f.name for f in self._group_fields)
        aggs = ", ".join(a.name for a in self.aggregates)
        fused = "" if self._pre_stage is None else \
            f", fused=[{self._pre_stage.describe_ops()}]"
        return (f"HashAggregateExec(mode={self.mode.value}, "
                f"keys=[{keys}], aggs=[{aggs}]{fused})")

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + self.describe()
        if self._pre_stage is not None:
            # EXPLAIN prints the fusion group's member operators
            for m in self._pre_stage.members:
                s += "\n" + "  " * (indent + 1) + "* " + m.describe()
        for c in self._children:
            s += "\n" + c.tree_string(indent + 1)
        return s

    @property
    def fused_members(self):
        """(describe, MetricSet) per fused member op, for the
        EXPLAIN-with-metrics breakdown; empty when unfused."""
        if self._pre_stage is None:
            return []
        return [(m.describe(), m.metrics)
                for m in self._pre_stage.members]

    def cache_scope(self):
        from spark_rapids_tpu.exprs.base import fingerprint
        return (self.mode.name, fingerprint(self._bound_groups),
                fingerprint(self._funcs),
                fingerprint(getattr(self, "_bound_inputs", None)),
                fingerprint(self._inter_types),
                fingerprint(self._child_schema),
                self._pre_stage.fingerprint()
                if self._pre_stage is not None else ("~",))

    def _make_ctx(self, columns, cap, num_rows, mask=None):
        """Kernel-trace eval context; with a fused pre-stage the raw
        child columns first flow through the composed project/filter
        DAG inside the SAME jit (plan/fusion.py eval_stage_ctx)."""
        ctx = make_eval_context(columns, cap, num_rows, mask)
        if self._pre_stage is not None:
            from spark_rapids_tpu.plan import fusion as FZ
            ctx = FZ.eval_stage_ctx(self._pre_stage, ctx)
        return ctx

    def _charge_pre_stage(self, t0: Optional[float]) -> None:
        """Fused-member metric/event bookkeeping per dispatched batch;
        the FIRST dispatch (trace + compile happen synchronously on a
        jit's first call) also emits the profiler's stage_fused
        event."""
        if self._pre_stage is None:
            return
        import time as _time
        for m in self._pre_stage.members:
            m.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
        if not self._fused_event_done and t0 is not None:
            self._fused_event_done = True
            from spark_rapids_tpu.utils import profile as P
            P.event(P.EV_STAGE_FUSED,
                    members=self._pre_stage.member_names()
                    + [type(self).__name__],
                    exprs=self._pre_stage.expr_count,
                    compile_ms=round(
                        (_time.perf_counter() - t0) * 1e3, 2))

    # -- kernels ------------------------------------------------------------
    #: past this many estimated packed sort words the grouping sort
    #: routes through the 2-word murmur3 hash lane — wide key sets
    #: (string groupers emit one 9-bit key per char position) would
    #: otherwise trace a sort chain whose XLA compile time and memory
    #: scale with total key WIDTH (TPC-DS q64's 15-key string grouper
    #: is ~100 words: minutes of compile, GBs of arena, per schema)
    #: alias of the shared routing threshold so both grouping
    #: lanes (aggregate group-by, window partition-by) tune together
    from spark_rapids_tpu.ops.sort_encode import \
        HASH_GROUP_MIN_WORDS as HASH_GROUP_MIN_WORDS

    def _use_hash_grouping(self, batch: ColumnarBatch) -> bool:
        # the deopt retry must produce guaranteed-valid results (there
        # is no second retry — see utils/checks.py), so it always takes
        # the lexicographic lane, like _compact_groups
        if getattr(self, "_hash_group_disabled", False) or CK.is_retrying():
            return False
        from spark_rapids_tpu import config as C
        if not C.get_active_conf()[C.HASH_GROUPING_ENABLED]:
            return False
        # with a fused pre-stage the batch carries the RAW child
        # columns, so ordinal-based column inspection would read the
        # wrong column — route through the dtype-only estimate
        return wide_key_set(self._bound_groups,
                            None if self._pre_stage is not None
                            else batch,
                            self._child_schema,
                            self.HASH_GROUP_MIN_WORDS)

    #: cap bound for the banded lane: first-row indices travel as two
    #: 11-bit f32 limbs (exact one-hot sums), covering rows < 2^22;
    #: f32-exact group counts need < 2^24 anyway
    BANDED_MAX_CAP = 1 << 22

    def _measure_types(self, phase: str) -> list:
        """The type each Sum / Average accumulates from in this phase:
        its input's in the update phase, its sum intermediate's in the
        merge phase (an Average's is FLOAT64 whatever it averages)."""
        sums = [i for i, f in enumerate(self._funcs)
                if type(f).__name__ in ("Sum", "Average")]
        if phase == "merge":
            return [self._inter_types[i][0] for i in sums]
        return [self._bound_inputs[i][0].data_type(self._child_schema)
                for i in sums]

    def _banded_float_measures(self, phase: str) -> bool:
        """True when this exec+phase would put FLOAT32 values through
        the f32 banded accumulator (needs the variableFloatAgg
        tolerance: the order of the additions varies; integral
        measures ride the exact-or-deopt certificate instead)."""
        return any(t.is_floating for t in self._measure_types(phase))

    def _use_banded(self, batch: ColumnarBatch, phase: str) -> bool:
        if not self._banded_qual or \
                getattr(self, "_banded_disabled", False):
            return False
        if CK.is_retrying():
            # the deopt retry must be guaranteed-valid; certificate
            # lanes cannot be the last resort
            return False
        if any(t == T.FLOAT64 for t in self._measure_types(phase)):
            # the lane accumulates in float32 on the MXU: a FLOAT64
            # measure takes the sort-segment lane, which sums in
            # float64, whatever the lane switches say (SQL's answer is
            # not a switch; variableFloatAgg licenses an order, not a
            # precision)
            return False
        from spark_rapids_tpu import config as C
        conf = C.get_active_conf()
        if not conf[C.BANDED_GROUPBY_ENABLED]:
            return False
        cap = batch.capacity
        if cap % 128 or cap > self.BANDED_MAX_CAP:
            return False
        if self._banded_float_measures(phase) and \
                not conf[C.VARIABLE_FLOAT_AGG]:
            return False
        return True

    def _disable_banded(self) -> None:
        self._banded_disabled = True
        me = getattr(self, "_merge_exec", None)
        if me is not None:
            me._banded_disabled = True

    def _register_banded_check(self, cert, checks: tuple) -> tuple:
        """Deferred exactness deopt for the banded lane (None = lane
        not taken, nothing to check)."""
        return CK.register_deopt(cert,
                                 f"bandedGroupby[exec {self.exec_id}]",
                                 self._disable_banded, checks)

    def _disable_hash_grouping(self) -> None:
        # a 64-bit murmur3 collision between two distinct key tuples
        # (detected exactly by the in-kernel boundary/hash cross-check)
        # deopts this exec to the lexicographic lane for good
        self._hash_group_disabled = True

    def _groupby_kernel(self, batch: ColumnarBatch, phase: str,
                        wcap: Optional[int] = None):
        """phase: 'update' (raw inputs) or 'merge' (intermediates).
        `wcap`: compact GROUP width — when set, every per-group gather
        and output column runs at wcap instead of full row capacity
        (a 2M-row batch with 1K groups spent ~1/3 of its kernel on
        full-capacity group materialization), and the kernel reports
        `num_groups > wcap` as a deferred excess flag (same
        escalate-and-retry contract as _compact_groups)."""
        use_hash = self._use_hash_grouping(batch)
        use_banded = self._use_banded(batch, phase)
        out_cap = wcap if wcap is not None else batch.capacity
        few_slots = 0 if use_banded else self._few_groups_slots(out_cap)
        self._lane = ("banded" if use_banded else
                      "few-or-sort" if few_slots else "sort-segment")
        key = ("agg", phase, use_hash, use_banded, wcap, few_slots,
               batch_signature(batch))
        kp_members = (self._pre_stage.member_names()
                      if self._pre_stage is not None else None)

        def build():
            cap = batch.capacity

            @named_jit(f"agg-{phase}")
            def kernel(columns, num_rows, mask=None):
                ctx = self._make_ctx(columns, cap, num_rows, mask)
                keys = [e.eval(ctx) for e in self._bound_groups]
                if phase == "update":
                    inputs_per_f = [
                        [e.eval(ctx) for e in bins]
                        for bins in self._bound_inputs]
                else:
                    inputs_per_f = [
                        [ctx.columns[i] for i in range(lo, hi)]
                        for lo, hi in self._inter_offsets]

                def sorted_body():
                    return self._sorted_groups(
                        phase, use_hash, use_banded, wcap, keys,
                        inputs_per_f, ctx.row_mask)

                if not few_slots:
                    return sorted_body() + (None,)
                # the batch says how many groups it has: up to
                # `few_slots` of them are named by exact key equality,
                # and only a batch with more pays the sort
                slot, leaders, n_few, overflow = elect_group_leaders(
                    keys, ctx.row_mask, few_slots)

                def few_body():
                    return self._few_groups(
                        phase, use_hash, wcap, keys, inputs_per_f,
                        ctx.row_mask, slot, leaders, n_few)

                return lax.cond(overflow, sorted_body, few_body) + (
                    (~overflow).astype(jnp.int32),)

            return kernel

        # update-lane kernels of a fused aggregate carry the composed
        # pre-stage's member names, so the kernel table attributes the
        # inlined project/filter work to this kernel too
        return self.kernels.get_or_build(
            key, build,
            meta=self.kp_meta(f"agg-{phase}", members=kp_members))

    #: a batch with at most this many groups is grouped without a sort
    #: (`_few_groups`): the grouped kernel spends up to this many rounds
    #: of exact key comparison on finding that out, so a batch of many
    #: groups pays them on top of its sort.  Not a switch: no conf key
    #: reads it, and either body gives the same groups
    FEW_GROUPS_MAX = 16

    def _few_groups_slots(self, out_cap: int) -> int:
        """How many slots the kernel's few-groups body gets, 0 where it
        is not built: every function must leave HOW a group is reduced
        to the context (`reduces_through_scans`)."""
        if not all(f.reduces_through_scans(ts)
                   for f, ts in zip(self._funcs, self._inter_types)):
            return 0
        return min(self.FEW_GROUPS_MAX, out_cap)

    def _sorted_groups(self, phase, use_hash, use_banded, wcap, keys,
                       inputs_per_f, row_mask):
        """The grouped kernel's sort body: rows sorted by group (hash
        or lexicographic words), one stacked gather of the value
        streams, one segmented scan a round.  Returns (columns,
        num_groups, collision, excess, cert)."""
        cap = row_mask.shape[0]
        out_cap = wcap if wcap is not None else cap
        if use_hash:
            perm, sorted_valid, bounds, collision = \
                hash_sort_bounds([(k, True, True) for k in keys],
                                 row_mask)
        else:
            perm, sorted_valid, bounds, _ = sort_with_bounds(
                [(k, True, True) for k in keys], row_mask)
            collision = None
        seg_ids = jnp.cumsum(bounds.astype(jnp.int32)) - 1
        num_groups = bounds.sum().astype(jnp.int32)
        excess = (num_groups > out_cap) if wcap is not None \
            else None
        grp_valid = jnp.arange(out_cap) < num_groups

        flat = [v for ins in inputs_per_f for v in ins]
        # grouped-stream reorder: ALL 4-byte value streams plus
        # the packed validity word ride ONE stacked gather and
        # f64 streams another (random access costs ~70ns per
        # ROW, not per byte — a 4-measure agg paid 4 gathers
        # here before)
        from spark_rapids_tpu.columnar.vector import \
            gather_columns_grouped
        sorted_flat = gather_columns_grouped(flat, perm,
                                             sorted_valid)
        it = iter(sorted_flat)
        sorted_per_f = [[next(it) for _ in ins]
                        for ins in inputs_per_f]

        if use_banded:
            out_cols, first_idx, cert = self._banded_aggregate(
                phase, sorted_per_f, sorted_valid, bounds,
                seg_ids, grp_valid, cap, out_cap)
            rep_idx = jnp.take(perm, first_idx, mode="clip")
            key_cols = [k.gather(rep_idx, grp_valid)
                        for k in keys]
            return (key_cols + out_cols, num_groups, collision,
                    excess, cert)

        # group key representatives: first row of each segment
        from spark_rapids_tpu.ops.sort_encode import \
            masked_positions
        first_idx = masked_positions(bounds, out_cap,
                                     fill_value=cap - 1)
        # per-segment LAST sorted row: one before the next
        # segment's start; the last real segment (which also
        # absorbs trailing invalid rows' segment ids) ends at
        # cap-1 — aggregates fill invalid rows with identities
        nxt = jnp.concatenate(
            [first_idx[1:],
             jnp.full((1,), cap, first_idx.dtype)])
        ends = jnp.where(jnp.arange(out_cap) >= num_groups - 1,
                         cap - 1, nxt - 1).astype(jnp.int32)
        actx = AggContext(seg_ids, cap, sorted_valid, bounds,
                          ends, out_capacity=out_cap)

        out_cols = []
        # representatives via index COMPOSITION: one i32 gather
        # (perm at first_idx) + one gather per key column — the
        # sorted_keys detour re-gathered every key column at
        # full cap twice (random-access streams are the
        # dominant kernel cost at ~70ns/row on this chip)
        rep_idx = jnp.take(perm, first_idx, mode="clip")
        for k in keys:
            out_cols.append(k.gather(rep_idx, grp_valid))

        # ONE cross-function segmented scan per round (each
        # function's operands batch into a shared _segscan —
        # a q1-shaped aggregate ran 8 separate 2M-row scan
        # dispatches at ~100ms each before)
        for outs in run_agg_phase(actx, self._funcs, sorted_per_f,
                                  phase):
            out_cols.extend(
                ColumnVector(o.dtype, o.data,
                             o.validity & grp_valid,
                             o.lengths) for o in outs)
        return out_cols, num_groups, collision, excess, None

    def _few_groups(self, phase, use_hash, wcap, keys, inputs_per_f,
                    row_mask, slot, leaders, num_groups):
        """The grouped kernel's few-groups body, for a batch whose
        groups `elect_group_leaders` could all name: no sort, no gather
        of the value streams, no scan.  The measures stay in row order,
        every function reduces under its slot's mask
        (`AggContext.few_groups`), a group's keys are its leader's, and
        the groups come out in order of first appearance at `slots`
        rows, padded to the sort body's `out_cap`.  Membership was
        decided by exact equality, so there is nothing to check: the
        collision and excess flags are the sort body's, never set."""
        cap, slots = row_mask.shape[0], leaders.shape[0]
        out_cap = wcap if wcap is not None else cap
        live = jnp.arange(slots) < num_groups
        actx = AggContext(slot, cap, row_mask, out_capacity=slots,
                          few_groups=True, num_groups=num_groups)
        out_cols = [k.gather(leaders, live) for k in keys]
        for outs in run_agg_phase(actx, self._funcs, inputs_per_f,
                                  phase):
            out_cols.extend(
                ColumnVector(o.dtype, o.data, o.validity & live,
                             o.lengths) for o in outs)
        never = jnp.zeros((), bool)
        return ([c.with_capacity(out_cap) for c in out_cols], num_groups,
                never if use_hash else None,
                never if wcap is not None else None, None)

    def _banded_aggregate(self, phase, sorted_per_f, sorted_valid,
                          bounds, seg_ids, grp_valid, cap, out_cap):
        """Banded windowed-MXU aggregation over the sorted rows (see
        ops/grouped_window.py): every Sum/Count/Average measure —
        plus two 11-bit first-row-index limbs for key recovery —
        accumulates per group in ONE windowed kernel + merge matmul.
        Replaces masked_positions (a second full sort at high group
        counts), the segmented scans, and the full-width ends
        machinery.  Returns (agg columns, first_idx, cert_flag):
        cert_flag (device bool or None) reports an integral measure
        whose f32 accumulation may have rounded — the caller registers
        it as a deferred deopt (reference parity: cuDF hash groupby is
        exact; this lane is exact-or-retry)."""
        from spark_rapids_tpu.ops.grouped_window import window_group_sums
        from spark_rapids_tpu.ops.pallas_kernels import _on_tpu

        measures: list = []
        specs: list = []
        cert_ids: list = []

        def add(arr) -> int:
            measures.append(arr.astype(jnp.float32))
            return len(measures) - 1

        def value_measure(p: ColumnVector, ok):
            """f32 measure of a column's values, zeroed where not ok;
            prefers the i32 narrow shadow (64-bit elementwise is
            50-100x slower on this chip)."""
            if p.narrow is not None and not p.dtype.is_floating:
                raw = p.narrow
            else:
                raw = p.data
            v32 = raw.astype(jnp.float32)
            return jnp.where(ok, v32, jnp.float32(0))

        rv = sorted_valid
        for f, ins, its in zip(self._funcs, sorted_per_f,
                               self._inter_types):
            nm = type(f).__name__
            if nm == "Count":
                if phase == "merge":
                    (p,) = ins
                    ok = p.validity & rv
                    mi = add(value_measure(p, ok))
                    cert_ids.append(mi)  # counts are nonnegative
                    specs.append(("count", mi, None))
                else:
                    ok = rv if f.child is None \
                        else (ins[0].validity & rv)
                    specs.append(("count", add(ok), None))
            elif nm == "Sum":
                (p,) = ins
                ok = p.validity & rv
                mi = add(value_measure(p, ok))
                fi = add(ok)
                if not its[0].is_floating:
                    cert_ids.append(add(jnp.abs(measures[mi])))
                specs.append(("sum", mi, fi))
            else:  # Average: intermediates (f64 sum, i64 count)
                if phase == "merge":
                    s_p, c_p = ins
                    ok = rv
                    ms = add(value_measure(s_p, ok))
                    mc = add(value_measure(c_p, ok))
                    cert_ids.append(mc)
                    specs.append(("avg", ms, mc))
                else:
                    (p,) = ins
                    ok = p.validity & rv
                    mi = add(value_measure(p, ok))
                    fi = add(ok)
                    if not p.dtype.is_floating:
                        cert_ids.append(add(jnp.abs(measures[mi])))
                    specs.append(("avg", mi, fi))

        isf32 = bounds.astype(jnp.float32)
        iota = jnp.arange(cap, dtype=jnp.int32)
        li = add((iota & 2047).astype(jnp.float32) * isf32)
        hi = add((iota >> 11).astype(jnp.float32) * isf32)

        sums = window_group_sums(seg_ids, tuple(measures),
                                 out_cap=out_cap, capacity=cap,
                                 interpret=not _on_tpu())

        def col(i):
            return sums[:, i]

        # exactly one first-row hit per group -> limb sums are the limb
        # values themselves, exact in f32
        first_idx = jnp.clip(
            (col(li) + col(hi) * jnp.float32(2048)).astype(jnp.int32),
            0, cap - 1)
        cert = None
        if cert_ids:
            bad = jnp.zeros((), bool)
            thresh = jnp.float32(1 << 23)
            for ci in cert_ids:
                bad = bad | jnp.any(
                    jnp.where(grp_valid, col(ci), 0.0) >= thresh)
            cert = bad

        out_cols: list = []
        for (kind, mi, fi), its in zip(specs, self._inter_types):
            if kind == "count":
                c = jnp.round(col(mi)).astype(jnp.int64)
                out_cols.append(ColumnVector(T.INT64, c, grp_valid))
            elif kind == "sum":
                has = (col(fi) > 0) & grp_valid
                dt = its[0]
                if dt.is_floating:
                    data = col(mi).astype(jnp.float64)
                else:
                    data = jnp.round(col(mi)).astype(jnp.int64)
                out_cols.append(ColumnVector(dt, data, has))
            else:  # avg: (f64 sum, i64 count)
                out_cols.append(ColumnVector(
                    T.FLOAT64, col(mi).astype(jnp.float64), grp_valid))
                out_cols.append(ColumnVector(
                    T.INT64, jnp.round(col(fi)).astype(jnp.int64),
                    grp_valid))
        return out_cols, first_idx, cert

    def _kernel_compact_cap(self, batch: ColumnarBatch) -> Optional[int]:
        """Compact group width for the kernel, or None (full-width
        output).  Mirrors _compact_groups' policy: the deopt retry is
        the last chance and must be guaranteed-valid, so it always runs
        uncompacted; escalation is learned per exec instance."""
        if CK.is_retrying():
            return None
        tc = getattr(self, "_compact_cap", self.COMPACT_GROUPS_CAP)
        if tc > self.COMPACT_GROUPS_MAX or batch.capacity <= tc:
            return None
        return tc

    def _register_excess_check(self, excess, wcap: Optional[int],
                               checks: tuple) -> tuple:
        if excess is None:
            return checks
        chk = CK.register(CK.BatchCheck(
            excess, origin=f"aggCompactGroups[exec {self.exec_id}]",
            recover=lambda cap=wcap: self._escalate_compact(cap)))
        return checks + (chk,)

    def _register_collision_check(self, collision, checks: tuple) -> tuple:
        """Deferred 64-bit-collision deopt for the hash-grouping lane
        (None = lexicographic lane, nothing to check)."""
        return CK.register_deopt(collision,
                                 f"hashGroupby[exec {self.exec_id}]",
                                 self._disable_hash_grouping, checks)

    def _evaluate_kernel(self, batch: ColumnarBatch):
        """Final projection: intermediates -> results (no regrouping)."""
        key = ("agg-eval", batch_signature(batch))

        def build():
            cap = batch.capacity
            funcs = self._funcs
            n_groups_cols = len(self._group_fields)

            @named_jit("agg-eval")
            def kernel(columns, num_rows):
                out = list(columns[:n_groups_cols])
                off = n_groups_cols
                for f in funcs:
                    n = f.num_intermediates
                    parts = columns[off: off + n]
                    off += n
                    out.append(f.evaluate(parts, self._child_schema))
                return out

            return kernel

        return self.kernels.get_or_build(
            key, build, meta=self.kp_meta("agg-eval"))

    # -- dictionary fast path (conf-gated) -----------------------------------
    def _dict_plan(self):
        """Static qualification for the sort-free dictionary path:
        1..3 integral keys (multi-key folds into one composite slot id),
        Sum/Count/Average over FLOAT32 inputs (variableFloatAgg-gated
        f32 accumulation; never FLOAT64) or INTEGRAL inputs
        (exact-or-deopt: an in-kernel f32-exactness certificate, no
        conf gate).
        Returns (plan, measures) or None."""
        if self.mode == AggMode.FINAL or \
                not 1 <= len(self._bound_groups) <= 3:
            return None
        if not all(f.dtype.is_integral for f in self._group_fields):
            return None
        plan, measures = [], []
        self._dict_float = False
        for f, bins in zip(self._funcs, self._bound_inputs):
            name = type(f).__name__
            if name == "Count":
                if bins:
                    plan.append(("count_expr", len(measures)))
                    measures.append(("flag", bins[0]))
                else:
                    plan.append(("count_star", None))
            elif name in ("Sum", "Average"):
                dt = bins[0].data_type(self._child_schema)
                if dt == T.FLOAT64:
                    # the kernel accumulates in float32: a FLOAT64
                    # measure never qualifies (it sums in float64 on
                    # the sort-segment lane), whatever the switches say
                    return None
                if dt.is_floating:
                    self._dict_float = True
                    plan.append((name.lower(), len(measures)))
                    measures.append(("val", bins[0]))
                    measures.append(("flag", bins[0]))
                elif dt.is_integral:
                    # exact-or-deopt: f32 accumulation of integers is
                    # EXACT while every intermediate fits 2^24, which
                    # the kernel certifies per group by accumulating
                    # sum(|v|) alongside (inexactness cannot hide:
                    # f32 adds of nonnegative ints round monotonically,
                    # so a true sum >= 2^23 reads >= ~2^23).  No
                    # variableFloatAgg gate — results are bit-exact or
                    # the deferred check deopts to the sort lane.
                    plan.append((name.lower() + "_int", len(measures)))
                    measures.append(("val", bins[0]))
                    measures.append(("flag", bins[0]))
                    measures.append(("absval", bins[0]))
                else:
                    return None
            else:
                return None
        return plan, measures

    def _dict_groupby_batch(self, batch: ColumnarBatch):
        """Sort-free grouped aggregation (reference: the role cuDF's hash
        groupby plays under `aggregate.scala:312` vs the sort-based
        fallback): when the integral key ranges (a single key, or the
        composite product of up to three keys) fit the dictionary
        budget at RUNTIME, the whole batch goes through ONE fused
        dispatch — key-window slots, Pallas one-hot grouped-sum
        (ops/pallas_kernels.grouped_sum_pallas), and the partial-batch
        finalize, all inside one jit.  A one-time first-batch probe
        sizes the padded dictionary; later batches compute their own
        window base (kmin) device-side and report overflow instead of
        paying a probe round-trip, so the steady state is one dispatch
        plus one tiny readback per batch.

        Planner-automatic: default-on (spark.rapids.tpu.dictGroupby
        .enabled) with float Sum/Average additionally gated on
        variableFloatAgg.enabled — the kernel accumulates f32, a
        variableFloatAgg-class tolerance (ADVICE r2).  Count-only plans
        are exact and need no float gate.  Returns the partial-layout
        batch or None (caller falls back to the sort kernel)."""
        from spark_rapids_tpu import config as C
        conf = C.get_active_conf()
        if not conf[C.DICT_GROUPBY_ENABLED] or self._dict_qual is None:
            return None
        if self._dict_float and not conf[C.VARIABLE_FLOAT_AGG]:
            return None
        if batch.capacity >= (1 << 24) or batch.capacity % 128:
            return None  # f32 counts exact below 2^24; kernel needs
            # lane-aligned capacities
        if self._dict_range_misses >= 3:
            # this exec's keys keep spanning past the budget: stop
            # trying (and stop paying discarded fast dispatches)
            return None

        nk = len(self._bound_groups)
        if self._dict_gpad is None:
            probe = self.kernels.get_or_build(
                ("dict-probe", nk, batch_signature(batch)),
                lambda: named_jit(
                    "agg-dict-probe",
                    self._build_dict_probe(batch.capacity)),
                meta=self.kp_meta("agg-dict-probe"))
            if batch.sparse is not None:
                kmins, kmaxs = probe(batch.columns, batch.num_rows_i32,
                                     batch.sparse)
            else:
                kmins, kmaxs = probe(batch.columns, batch.num_rows_i32)
            import numpy as _np
            from spark_rapids_tpu.utils import checks as CK
            CK.note_host_sync("agg.dict_probe", nbytes=16 * nk)
            kmins = _np.asarray(kmins).reshape(-1)
            kmaxs = _np.asarray(kmaxs).reshape(-1)
            spans = [max(int(hi) - int(lo) + 1, 1) if hi >= lo else 1
                     for lo, hi in zip(kmins, kmaxs)]
            budget = int(conf[C.DICT_GROUPBY_MAX_GROUPS])
            if nk == 1:
                if spans[0] > budget:
                    self._dict_range_misses += 1
                    return None
                # bucket the padded width so compiles amortize
                self._dict_gpad = max(8, int(bucket_capacity(spans[0])))
            else:
                # per-key ~12.5% headroom (later batches drift), width
                # includes a null slot per key; composite product must
                # fit the budget
                pads = [max(4, -(-(s + s // 8) // 4) * 4)
                        for s in spans]
                total = 1
                for p in pads:
                    total *= p + 1
                if total > budget:
                    self._dict_range_misses += 1
                    return None
                self._dict_gpad = tuple(pads)
        g_pad = self._dict_gpad

        kp_members = (self._pre_stage.member_names()
                      if self._pre_stage is not None else None)
        if nk == 1:
            fused = self.kernels.get_or_build(
                ("dict-fused", g_pad, batch_signature(batch)),
                lambda: named_jit(
                    "agg-dict-fused",
                    self._build_dict_fused(batch.capacity, g_pad)),
                meta=self.kp_meta("agg-dict-fused",
                                  members=kp_members))
        else:
            fused = self.kernels.get_or_build(
                ("dict-fused-multi", g_pad, batch_signature(batch)),
                lambda: named_jit(
                    "agg-dict-fused-multi",
                    self._build_dict_fused_multi(
                        batch.capacity, list(g_pad))),
                meta=self.kp_meta("agg-dict-fused-multi",
                                  members=kp_members))
        if batch.sparse is not None:
            cols, n, excess = fused(batch.columns, batch.num_rows_i32,
                                    batch.sparse)
        else:
            cols, n, excess = fused(batch.columns, batch.num_rows_i32)
        from spark_rapids_tpu.utils import checks as CK
        check = CK.register(CK.BatchCheck(
            excess, f"dictGroupby[exec {self.exec_id}]",
            self._disable_dict_path))
        self._lane = "dict"
        return ColumnarBatch(self._partial_schema(), list(cols), n,
                             batch.checks + (check,))

    def _disable_dict_path(self) -> None:
        self._dict_range_misses = 1 << 20

    #: static budget of per-batch overflow rows the fused kernel carries
    #: INLINE as singleton partial groups (exact — partial aggregation
    #: may emit duplicate keys; the final merge combines them).  Only
    #: when a batch overflows past this does the deferred excess check
    #: fire and deopt the query.
    DICT_OVERFLOW_BUDGET = 1024

    @staticmethod
    def _eval_dict_measures(ctx, measures, rows):
        """Shared by both fused dict kernels: evaluate measures into
        (f32 kernel inputs, raw (value, valid) pairs for overflow
        rows).  Raw values stay UN-masked and UN-cast: full-width f64
        selects/casts are slow emulated ops; mask+cast happen after the
        (tiny) overflow gather."""
        vals, raw = [], []
        for kind, e in measures:
            v = e.eval(ctx)
            good = v.validity & rows
            if kind in ("val", "absval"):
                v32 = (v.narrow if v.narrow is not None
                       else v.data.astype(jnp.float32))
                v32 = jnp.asarray(v32, jnp.float32)
                if kind == "absval":
                    # certificate input only — overflow singletons read
                    # the paired "val" measure's raw entry, so this
                    # raw slot is a placeholder keeping mi alignment
                    v32 = jnp.abs(v32)
                vals.append(jnp.where(good, v32, jnp.float32(0)))
                raw.append((None, good) if kind == "absval"
                           else (v.data, good))
            else:
                vals.append(good.astype(jnp.float32))
                raw.append((good, good))
        return vals, raw

    @staticmethod
    def _compact_dict_overflow(ovf_mask, ovf_cnt, cap, ovf_budget):
        """Shared overflow-row compaction (first ovf_budget overflow
        rows).  The compaction (a top_k over the full capacity, ~67ms
        at 2M) is gated behind lax.cond: the common case — zero
        overflow — pays only the (fused) mask/count it needed anyway."""
        def _compact():
            iota = jnp.arange(cap, dtype=jnp.int32)
            keyv = jnp.where(ovf_mask, iota, jnp.iinfo(jnp.int32).max)
            neg, _ = jax.lax.top_k(-keyv, ovf_budget)
            return jnp.clip(-neg, 0, cap - 1)

        return jax.lax.cond(
            ovf_cnt > 0, _compact,
            lambda: jnp.full(ovf_budget, cap - 1, jnp.int32))

    @staticmethod
    def _emit_dict_partials(plan, raw, sums_at, cnt_mixed, wi, oi,
                            from_win, valid_out):
        """Shared finalize: window groups + inline overflow singletons
        -> partial agg columns.  `sums_at(mi)` yields the compacted
        window column for kernel measure mi.  Invalid cells are masked
        AFTER the tiny overflow gather so they read as 0, not garbage
        (downstream merges may touch masked data)."""
        out = []
        inexact = jnp.bool_(False)
        for kind, mi in plan:
            if kind == "count_star":
                out.append(ColumnVector(T.INT64, cnt_mixed, valid_out))
                continue
            if kind == "count_expr":
                win_c = jnp.round(sums_at(mi)).astype(jnp.int64)
                _, good_o = raw[mi]
                ovf_c = jnp.take(good_o, oi).astype(jnp.int64)
                out.append(ColumnVector(
                    T.INT64, jnp.where(from_win, jnp.take(win_c, wi),
                                       ovf_c), valid_out))
                continue
            s_w = sums_at(mi)
            f_w = jnp.round(sums_at(mi + 1)).astype(jnp.int64)
            val_o, good_o = raw[mi]
            some = jnp.where(from_win, jnp.take(f_w > 0, wi),
                             jnp.take(good_o, oi)) & valid_out
            if kind in ("sum_int", "average_int"):
                # exactness certificate: every f32 add was exact iff
                # the group's sum(|v|) stayed under 2^24 (threshold
                # 2^23 leaves margin for the certificate's own
                # rounding); past it the deferred check deopts
                inexact = inexact | jnp.any(
                    sums_at(mi + 2) >= jnp.float32(1 << 23))
                win_s = jnp.round(s_w).astype(jnp.int64)
                ovf_s = jnp.take(val_o, oi).astype(jnp.int64)
                si = jnp.where(some,
                               jnp.where(from_win, jnp.take(win_s, wi),
                                         ovf_s), jnp.int64(0))
                if kind == "sum_int":
                    out.append(ColumnVector(T.INT64, si, some))
                else:  # average over ints: (f64 sum, i64 count)
                    out.append(ColumnVector(
                        T.FLOAT64, si.astype(jnp.float64), some))
                    cnt_col = jnp.where(
                        from_win, jnp.take(f_w, wi),
                        jnp.take(good_o, oi).astype(jnp.int64))
                    out.append(ColumnVector(T.INT64, cnt_col, valid_out))
                continue
            s = jnp.where(
                some,
                jnp.where(from_win, jnp.take(s_w, wi),
                          jnp.take(val_o, oi).astype(jnp.float64)),
                jnp.float64(0))
            out.append(ColumnVector(T.FLOAT64, s, some))
            if kind == "average":
                cnt_col = jnp.where(
                    from_win, jnp.take(f_w, wi),
                    jnp.take(good_o, oi).astype(jnp.int64))
                out.append(ColumnVector(T.INT64, cnt_col, valid_out))
        return out, inexact

    def _build_dict_fused(self, cap: int, g_pad: int):
        """Sync-free fused dict kernel: ONE dispatch computes the key
        window (anchored at this batch's own device-side kmin), the
        Pallas one-hot grouped sum, the compacted partial batch, AND
        folds out-of-window rows in as inline singleton partial groups.
        Slot layout: [0, g_pad) dense key window, g_pad = null group,
        g_pad+1 = masked (overflow + padding).  Returns
        (columns, num_rows, excess_flag) — all device; nothing syncs."""
        from spark_rapids_tpu.ops.pallas_kernels import (_on_tpu,
                                                         grouped_sum_pallas)
        key_expr = self._bound_groups[0]
        plan, measures = self._dict_qual
        kdt = self._group_fields[0].dtype
        ovf_budget = min(self.DICT_OVERFLOW_BUDGET, cap)
        w_cap = g_pad + 1
        out_cap = int(bucket_capacity(w_cap + ovf_budget))
        interp = not _on_tpu()

        def fused(columns, num_rows, mask=None):
            ctx = self._make_ctx(columns, cap, num_rows, mask)
            k = key_expr.eval(ctx)
            ok = k.validity & ctx.row_mask
            if k.narrow is not None:
                # 32-bit fast lane: 64-bit elementwise ops are ~50-100x
                # slower on TPU (emulated).  The unsigned-difference
                # trick keeps the window test EXACT even if kd-kmin
                # overflows int32: both fit i32, so the true offset
                # fits u32.
                k32 = k.narrow
                kmin32 = jnp.min(jnp.where(ok, k32,
                                           jnp.iinfo(jnp.int32).max))
                offu = (k32 - kmin32).astype(jnp.uint32)
                in_win = ok & (offu < jnp.uint32(g_pad))
                off = offu.astype(jnp.int32)
                kmin = kmin32.astype(jnp.int64)
            else:
                kd64 = k.data.astype(jnp.int64)
                i64 = jnp.iinfo(jnp.int64)
                kmin = jnp.min(jnp.where(ok, kd64, i64.max))
                off = kd64 - kmin
                in_win = ok & (off >= 0) & (off < g_pad)
            slots = jnp.where(
                in_win, off,
                jnp.where(ctx.row_mask & ~k.validity, g_pad,
                          g_pad + 1)).astype(jnp.int32)
            ovf_mask = ok & ~in_win
            ovf_cnt = ovf_mask.sum().astype(jnp.int32)
            vals, raw = HashAggregateExec._eval_dict_measures(
                ctx, measures, ctx.row_mask)
            # row masking rides the SLOT sentinel (padding/filtered rows
            # -> g_pad+1, never counted), so the kernel's prefix bound is
            # the full capacity — mandatory for SPARSE inputs, whose live
            # rows are scattered past the popcount
            sums, counts = grouped_sum_pallas(
                slots, tuple(vals), jnp.int32(cap), n_groups=g_pad + 1,
                capacity=cap, interpret=interp)

            # window-group compaction: null group FIRST, then dense keys
            order = jnp.concatenate([jnp.asarray([g_pad]),
                                     jnp.arange(g_pad)])
            cnt_o = jnp.take(counts, order)
            sums_o = jnp.take(sums, order, axis=0)
            occupied = cnt_o > 0
            n_win = occupied.sum().astype(jnp.int32)
            (nz,) = jnp.nonzero(occupied, size=w_cap, fill_value=0)
            slot_w = jnp.take(order, nz)
            cnt_w = jnp.take(cnt_o, nz)
            oidx = HashAggregateExec._compact_dict_overflow(
                ovf_mask, ovf_cnt, cap, ovf_budget)
            n_out = n_win + jnp.minimum(ovf_cnt, ovf_budget)
            excess = ovf_cnt > ovf_budget

            i = jnp.arange(out_cap)
            valid_out = i < n_out
            from_win = i < n_win
            wi = jnp.clip(i, 0, w_cap - 1)
            oi = jnp.take(oidx, jnp.clip(i - n_win, 0, ovf_budget - 1))

            key_data = jnp.where(
                from_win,
                jnp.take((kmin + slot_w).astype(kdt.storage_dtype), wi),
                jnp.take(k.data, oi).astype(kdt.storage_dtype))
            key_valid = jnp.where(from_win,
                                  jnp.take(slot_w != g_pad, wi),
                                  jnp.take(k.validity, oi)) & valid_out
            out = [ColumnVector(kdt, key_data, key_valid)]
            cnt_mixed = jnp.where(from_win,
                                  jnp.take(cnt_w.astype(jnp.int64), wi),
                                  jnp.int64(1))
            cols_m, inexact = HashAggregateExec._emit_dict_partials(
                plan, raw, lambda mi: jnp.take(sums_o[:, mi], nz),
                cnt_mixed, wi, oi, from_win, valid_out)
            out.extend(cols_m)
            return out, n_out, excess | inexact
        return fused

    def _build_dict_probe(self, cap: int):
        key_exprs = list(self._bound_groups)

        def probe(columns, num_rows, mask=None):
            ctx = self._make_ctx(columns, cap, num_rows, mask)
            i64 = jnp.iinfo(jnp.int64)
            mins, maxs = [], []
            for e in key_exprs:
                k = e.eval(ctx)
                ok = k.validity & ctx.row_mask
                kd = k.data.astype(jnp.int64)
                mins.append(jnp.min(jnp.where(ok, kd, i64.max)))
                maxs.append(jnp.max(jnp.where(ok, kd, i64.min)))
            return jnp.stack(mins), jnp.stack(maxs)
        return probe

    def _build_dict_fused_multi(self, cap: int, pads: list):
        """Composite-key variant of `_build_dict_fused`: each integral
        key gets a dense window of `pads[i]` value slots + 1 null slot,
        anchored at the batch's own device-side per-key minimum; the
        per-key slots fold into ONE composite id (row-major strides)
        that feeds the same Pallas one-hot grouped sum.  Rows outside
        ANY key's window become inline singleton partial groups exactly
        like the single-key path."""
        from spark_rapids_tpu.ops.pallas_kernels import (_on_tpu,
                                                         grouped_sum_pallas)
        key_exprs = list(self._bound_groups)
        kdts = [f.dtype for f in self._group_fields]
        plan, measures = self._dict_qual
        nk = len(key_exprs)
        widths = [p + 1 for p in pads]  # value slots + null slot
        strides = [1] * nk
        for i in range(nk - 2, -1, -1):
            strides[i] = strides[i + 1] * widths[i + 1]
        G = strides[0] * widths[0]
        ovf_budget = min(self.DICT_OVERFLOW_BUDGET, cap)
        w_cap = G
        out_cap = int(bucket_capacity(G + ovf_budget))
        interp = not _on_tpu()

        def fused(columns, num_rows, mask=None):
            ctx = self._make_ctx(columns, cap, num_rows, mask)
            rows = ctx.row_mask
            combined = jnp.zeros(cap, jnp.int32)
            in_win = rows
            kmins = []
            ks = []
            for e, span, stride in zip(key_exprs, pads, strides):
                k = e.eval(ctx)
                ks.append(k)
                okk = k.validity & rows
                if k.narrow is not None:
                    k32 = k.narrow
                    kmin32 = jnp.min(jnp.where(
                        okk, k32, jnp.iinfo(jnp.int32).max))
                    offu = (k32 - kmin32).astype(jnp.uint32)
                    within = offu < jnp.uint32(span)
                    off = offu.astype(jnp.int32)
                    kmin = kmin32.astype(jnp.int64)
                else:
                    kd64 = k.data.astype(jnp.int64)
                    kmin = jnp.min(jnp.where(
                        okk, kd64, jnp.iinfo(jnp.int64).max))
                    off64 = kd64 - kmin
                    within = (off64 >= 0) & (off64 < span)
                    off = jnp.clip(off64, 0, span - 1
                                   ).astype(jnp.int32)
                # per-key slot: dense value slot, or the null slot
                slot_i = jnp.where(k.validity,
                                   jnp.where(within, off, 0),
                                   jnp.int32(span))
                key_ok = jnp.where(k.validity, within, True)
                in_win = in_win & key_ok
                combined = combined + slot_i * jnp.int32(stride)
                kmins.append(kmin)
            ovf_mask = rows & ~in_win
            ovf_cnt = ovf_mask.sum().astype(jnp.int32)
            slots = jnp.where(in_win, combined, G).astype(jnp.int32)
            vals, raw = HashAggregateExec._eval_dict_measures(
                ctx, measures, rows)
            sums, counts = grouped_sum_pallas(
                slots, tuple(vals), jnp.int32(cap), n_groups=G + 1,
                capacity=cap, interpret=interp)
            occupied = counts[:G] > 0
            n_win = occupied.sum().astype(jnp.int32)
            (nz,) = jnp.nonzero(occupied, size=w_cap, fill_value=0)
            slot_w = nz.astype(jnp.int32)
            cnt_w = jnp.take(counts[:G], nz)
            oidx = HashAggregateExec._compact_dict_overflow(
                ovf_mask, ovf_cnt, cap, ovf_budget)
            n_out = n_win + jnp.minimum(ovf_cnt, ovf_budget)
            excess = ovf_cnt > ovf_budget

            i = jnp.arange(out_cap)
            valid_out = i < n_out
            from_win = i < n_win
            wi = jnp.clip(i, 0, w_cap - 1)
            oi = jnp.take(oidx, jnp.clip(i - n_win, 0, ovf_budget - 1))

            out = []
            for ki in range(nk):
                comp = (slot_w // jnp.int32(strides[ki])) \
                    % jnp.int32(widths[ki])
                k = ks[ki]
                is_null_w = comp == pads[ki]
                kd_w = (kmins[ki] + comp.astype(jnp.int64)
                        ).astype(kdts[ki].storage_dtype)
                key_data = jnp.where(
                    from_win, jnp.take(kd_w, wi),
                    jnp.take(k.data, oi).astype(
                        kdts[ki].storage_dtype))
                key_valid = jnp.where(
                    from_win, jnp.take(~is_null_w, wi),
                    jnp.take(k.validity, oi)) & valid_out
                out.append(ColumnVector(kdts[ki], key_data, key_valid))
            cnt_mixed = jnp.where(from_win,
                                  jnp.take(cnt_w.astype(jnp.int64), wi),
                                  jnp.int64(1))
            cols_m, inexact = HashAggregateExec._emit_dict_partials(
                plan, raw, lambda mi: jnp.take(sums[:G, mi], nz),
                cnt_mixed, wi, oi, from_win, valid_out)
            out.extend(cols_m)
            return out, n_out, excess | inexact
        return fused

    # -- execution ----------------------------------------------------------
    #: optimistic capacity for compacted group batches: a sort-lane
    #: partial otherwise stays at INPUT capacity (the group count is a
    #: device scalar — syncing it is a blocking device round trip), so
    #: every downstream op (exchange split, concat, merge re-sort) pays
    #: multi-M-capacity kernels for a few thousand groups.  Group rows
    #: are prefix-compacted by the kernel, so the compaction is a cheap
    #: head slice + a deferred overflow check.  On overflow the cap
    #: ESCALATES (x4 per deopt-and-retry round, learned per exec
    #: instance) rather than disabling — e.g. TPCx-BB q27's ~26K groups
    #: settle on the 64K tier, still far under review capacities.
    COMPACT_GROUPS_CAP = 1 << 14
    COMPACT_GROUPS_MAX = 1 << 20

    def _escalate_compact(self, failed_cap: int) -> None:
        # one escalation per retry round: several batches' checks may
        # fail together, and each invokes recover
        if getattr(self, "_compact_cap", self.COMPACT_GROUPS_CAP) \
                == failed_cap:
            self._compact_cap = failed_cap * 4

    def process_partition(self, batches) -> Iterator[ColumnarBatch]:
        if not self.group_exprs:
            yield from self._reduction_path(batches)
            return

        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.memory import oocore as OC
        from spark_rapids_tpu.memory import retry as R
        from spark_rapids_tpu.utils import profile as P
        conf = C.get_active_conf()
        inter_fields = self._partial_schema()
        partials: list[ColumnarBatch] = []
        pending_bytes = 0
        runs: list = []
        external = False
        run_target = max(1, OC.window_bytes(conf) // OC.MERGE_FAN_IN)
        # the capacity every merge kernel call was given (`_merge_one`),
        # for the merge span's args (host-known; profiled queries only)
        given = None if P.tracer() is None else []

        def flush_state():
            """Compact the pending partials to one batch of groups and
            spill it through the host→disk tiers (merging partial agg
            state is key-idempotent, so spilled blocks re-merge later
            in any grouping)."""
            nonlocal partials, pending_bytes
            if not partials:
                return
            merged = partials[0] if len(partials) == 1 else \
                self._merge_partials(partials, inter_fields, given)
            runs.append(OC.spill_run(merged.dense(), label=self.name(),
                                     metrics=self.metrics, conf=conf))
            partials = []
            pending_bytes = 0

        with P.span(P.SPAN_GROUPBY_UPDATE) as sp:
            n_in = rows_in = 0
            for batch in batches:
                if not batch.maybe_nonempty():
                    continue
                if sp is not None:
                    n_in += 1
                    rows_in += P.known_rows([batch])
                with self.metrics.timed(M.TOTAL_TIME):
                    # per-batch grouping is row-local, so halves from a
                    # split-and-retry simply land as extra partials for
                    # the merge below (this phase is a known OOM hotspot)
                    pieces = list(self.oom_retry_batches(
                        batch, self._groupby_one,
                        label=f"{self.name()}.groupBatch"))
                partials.extend(pieces)
                pending_bytes += sum(R.estimate_batch_bytes(p)
                                     for p in pieces)
                if not external and OC.should_go_external(pending_bytes,
                                                          conf):
                    external = True
                    P.event(P.EV_OOCORE_DEGRADE, op=self.name(),
                            est_bytes=pending_bytes, algo="agg-spill")
                if external and pending_bytes > run_target:
                    flush_state()
            if sp is not None:
                sp.args = {
                    "lane": self._lane, "batches": n_in, "rows_in": rows_in,
                    "phase": "merge" if self.mode == AggMode.FINAL
                    else "update"}

        if not partials and not runs:
            return
        with P.span(P.SPAN_GROUPBY_MERGE) as sp:
            if runs:
                flush_state()
                merged = self._merge_spilled_state(runs, inter_fields,
                                                   conf, given)
            else:
                # concat + re-merge loop until one batch of groups
                # remains
                merged = partials[0] if len(partials) == 1 else \
                    self._merge_partials(partials, inter_fields, given)

            if self.mode == AggMode.PARTIAL:
                out = merged
            else:
                with self.metrics.timed(M.TOTAL_TIME):
                    # the final projection reads one merged group batch
                    # — no input to subdivide, so pressure spills +
                    # retries in place (no-split lane)
                    (out,) = tuple(self.oom_retry_batches(
                        merged, self._evaluate_one, split=False,
                        label=f"{self.name()}.evaluate"))
            if sp is not None:
                me = getattr(self, "_merge_exec", None)
                sp.args = {"lane": me._lane if me is not None else None,
                           "partials": len(partials),
                           "capacity_rows": max(given or (), default=0),
                           "rounds": len(given or ()),
                           # None: the count is still on the device
                           "groups": out._rows if out.num_rows_known
                           else None}
        if out.num_rows_known:
            out = out.with_capacity(bucket_capacity(out.num_rows))
        self.update_output_metrics(out)
        yield out

    def _groupby_one(self, batch: ColumnarBatch) -> ColumnarBatch:
        """One batch (or split piece) through the grouping kernel ->
        partial-layout batch.  The OOM harness reserves ahead of this."""
        import time as _time
        phase = "merge" if self.mode == AggMode.FINAL else "update"
        t0 = _time.perf_counter() if (
            self._pre_stage is not None
            and not self._fused_event_done) else None
        fast = self._dict_groupby_batch(batch)
        if fast is not None:
            self._charge_pre_stage(t0)
            return fast
        wcap = self._kernel_compact_cap(batch)
        kern = self._groupby_kernel(batch, phase, wcap)
        if batch.sparse is not None:
            cols, n, coll, excess, cert, few = kern(
                batch.columns, batch.num_rows_i32, batch.sparse)
        else:
            cols, n, coll, excess, cert, few = kern(
                batch.columns, batch.num_rows_i32)
        self._charge_pre_stage(t0)
        self._count_few_groups(few)
        checks = self._register_collision_check(coll, batch.checks)
        checks = self._register_excess_check(excess, wcap, checks)
        checks = self._register_banded_check(cert, checks)
        return ColumnarBatch(self._partial_schema(), list(cols), n,
                             checks)

    def _count_few_groups(self, few) -> None:
        """`few`: the kernel's word on which body ran (a device int32,
        1 = the few-groups body), None where the kernel has one body.
        Queued like a lazy row count, so it is read, if ever, in the
        stacked read that resolves this exec's metrics."""
        if few is not None:
            self.metrics.add(M.NUM_FEW_GROUPS_OFFERED, 1)
            self.metrics.add(M.NUM_FEW_GROUP_BATCHES, few)

    def _evaluate_one(self, merged: ColumnarBatch) -> ColumnarBatch:
        kern = self._evaluate_kernel(merged)
        cols = kern(merged.columns, merged.num_rows_i32)
        return ColumnarBatch(self._schema, list(cols), merged._rows,
                             merged.checks)

    def _get_merge_exec(self, inter_schema) -> "HashAggregateExec":
        """Cached internal FINAL-mode exec so merge kernels are compiled
        once per batch signature, not once per partition."""
        me = getattr(self, "_merge_exec", None)
        if me is None:
            me = HashAggregateExec(
                [GroupRef(i, f.dtype)
                 for i, f in enumerate(self._group_fields)],
                [AggAlias(f, a.name) for f, a in
                 zip(self._funcs, self.aggregates)],
                _SchemaOnly(inter_schema), mode=AggMode.FINAL)
            self._merge_exec = me
        return me

    def _merge_spilled_state(self, runs: list, inter_schema,
                             conf, given=None) -> ColumnarBatch:
        """Windowed re-merge of spilled partial-aggregation state: each
        pass reads back window-sized groups of runs, merges each to one
        compacted batch of groups, and re-spills until a single block
        remains.  Bounded by `oocore.maxRecursionDepth` passes — past
        it, a descriptive error, never a hang or partial data."""
        from spark_rapids_tpu import config as C
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
                        f"{self.name()}: spilled aggregation state "
                        f"still spans {len(runs)} blocks after "
                        f"{passes} merge passes "
                        f"(spark.rapids.memory.oocore.maxRecursionDepth"
                        f"={max_passes}) — raise the HBM budget or "
                        f"oocore.windowFraction")
                passes += 1
                self.metrics.add(M.NUM_EXTERNAL_MERGE_PASSES, 1)
                P.event(P.EV_OOCORE_MERGE_PASS, op=self.name(),
                        num_runs=len(runs))
                groups: list[list] = [[]]
                group_bytes = 0
                for r in runs:
                    # 2x: payload + merge scratch; each group takes at
                    # least 2 runs so every pass at least halves the
                    # run count (the inner split-retry lattice absorbs
                    # any window overshoot)
                    if (len(groups[-1]) >= 2
                            and group_bytes + 2 * r.nbytes > window):
                        groups.append([])
                        group_bytes = 0
                    groups[-1].append(r)
                    group_bytes += 2 * r.nbytes
                next_runs = []
                for group in groups:
                    W.maybe_hang("oocore-merge", conf)
                    batches = [r.read(self.metrics) for r in group]
                    merged = batches[0] if len(batches) == 1 else \
                        self._merge_partials(batches, inter_schema, given)
                    for r in group:
                        r.free()
                    hb.beat()
                    if len(groups) == 1:
                        return merged  # final merge: no re-spill
                    next_runs.append(OC.spill_run(
                        merged.dense(), label=self.name(),
                        metrics=self.metrics, conf=conf))
                runs = next_runs
        final = runs[0]
        batch = final.read(self.metrics)
        final.free()
        return batch

    def _merge_partials(self, partials, inter_schema,
                        given: Optional[list] = None) -> ColumnarBatch:
        """The partials of a partition made one batch of groups.  Each
        partial is compacted to `_compact_cap` slots with its group
        count on the device, so their lazy concat has the bucketed SUM
        of those capacities (46 partials of TPC-H q1 at SF1: 2^20 slots
        for 184 rows, and concat + merge at that size were 42% of the
        device's time and 203 s of a cold compile).  The merge is a
        barrier, so past one batch of padding the counts come to the
        host in one stacked read and the concat is tight: the rule of
        `HashJoinExec._concat_build`.  `given`, where the caller keeps
        one, collects the capacity every merge kernel call was handed
        (`_merge_one`)."""
        from spark_rapids_tpu import config as C
        # sparse_ok: the merge kernel takes a deferred-selection mask,
        # so the concat can stay gather-free
        with programs_of("agg"):
            rows_made_known(partials, "agg.merge", beyond=bucket_capacity(
                int(C.get_active_conf()[C.MAX_BATCH_ROWS])))
            merged = concat_batches(partials, sparse_ok=True)
        merge_exec = self._get_merge_exec(inter_schema)
        # the merge phase is the aggregate's known OOM hotspot: under
        # reservation failure the concatenated partials split in half
        # and each half merges independently — a group key may then
        # appear in several results, so >1 outputs re-merge (each round
        # shrinks toward the final group count, and the row floor
        # bounds the recursion)
        outs = list(self.oom_retry_batches(
            merged,
            lambda b: self._merge_one(merge_exec, b, inter_schema, given),
            label=f"{self.name()}.mergePartials"))
        if len(outs) == 1:
            return outs[0]
        if sum(o.num_rows for o in outs) >= merged.num_rows:
            # split-retry made no progress: every split half still held
            # (nearly) every group key, so re-merging the halves would
            # ping-pong at the same row count forever under a sustained
            # reservation failure (tiny hbmBudgetBytes).  Fall back to
            # one unreserved best-effort merge of the whole state — the
            # same escape hatch the split floor uses.
            log.warning(
                "%s.mergePartials: split-retry not converging "
                "(%d rows -> %d across %d outputs); merging unreserved",
                self.name(), merged.num_rows,
                sum(o.num_rows for o in outs), len(outs))
            with programs_of("agg"):
                whole = concat_batches(outs, sparse_ok=True)
            return self._merge_one(merge_exec, whole, inter_schema, given)
        return self._merge_partials(outs, inter_schema, given)

    def _merge_one(self, merge_exec, merged, inter_schema,
                   given: Optional[list] = None) -> ColumnarBatch:
        if given is not None:
            given.append(merged.capacity)
        wcap = self._kernel_compact_cap(merged)
        with self.metrics.timed(M.TOTAL_TIME):
            kern = merge_exec._groupby_kernel(merged, "merge", wcap)
            if merged.sparse is not None:
                cols, n, coll, excess, cert, few = kern(
                    merged.columns, merged.num_rows_i32, merged.sparse)
            else:
                cols, n, coll, excess, cert, few = kern(
                    merged.columns, merged.num_rows_i32)
        self._count_few_groups(few)
        checks = merge_exec._register_collision_check(coll, merged.checks)
        # escalation is learned on the OUTER exec (the merge exec is a
        # cached internal helper; the compact policy lives with self)
        checks = self._register_excess_check(excess, wcap, checks)
        checks = self._register_banded_check(cert, checks)
        return ColumnarBatch(inter_schema, list(cols), n, checks)

    def _partial_schema(self) -> T.Schema:
        if self.mode == AggMode.FINAL:
            return self._child_schema  # child already emits partial layout
        fields = list(self._group_fields)
        for a, ts in zip(self.aggregates, self._inter_types):
            for j, it in enumerate(ts):
                fields.append(T.Field(f"{a.name}#{j}", it))
        return T.Schema(tuple(fields))

    # -- no-group-key reduction (reference aggregate.scala reduction path) --
    def _reduction_path(self, batches) -> Iterator[ColumnarBatch]:
        inter_schema = self._partial_schema()
        partials = []
        phase = "merge" if self.mode == AggMode.FINAL else "update"

        def reduce_one(b: ColumnarBatch) -> ColumnarBatch:
            import time as _time
            t0 = _time.perf_counter() if (
                self._pre_stage is not None
                and not self._fused_event_done) else None
            kern = self._reduce_kernel(b, phase)
            if b.sparse is not None:
                cols = kern(b.columns, b.num_rows_i32, b.sparse)
            else:
                cols = kern(b.columns, b.num_rows_i32)
            self._charge_pre_stage(t0)
            return ColumnarBatch(inter_schema, list(cols), 1, b.checks)

        # no group-by span here: there are no groups, and every span a
        # trace reader keeps costs its reduction (q6's 13 kept spans a
        # query became 21 with them: +29 / +36 s of a traced run's wall)
        self._lane = "reduce"
        for batch in batches:
            with self.metrics.timed(M.TOTAL_TIME):
                # whole-batch reductions are row-local too: split halves
                # just add 1-row partials to the merge below
                partials.extend(self.oom_retry_batches(
                    batch, reduce_one, label=f"{self.name()}.reduce"))
        if not partials:
            # SQL: aggregate of empty input yields one row (e.g. COUNT=0)
            partials = [self._empty_partial(inter_schema)]
        # always merge (even a single partial): normalizes e.g. an
        # all-invalid empty-input count intermediate into a valid 0
        merged = self._merge_reduction(partials, inter_schema)
        if self.mode == AggMode.PARTIAL:
            out = merged
        else:
            kern = self._evaluate_kernel(merged)
            cols = kern(merged.columns, merged.num_rows_i32)
            out = ColumnarBatch(self._schema, list(cols), 1, merged.checks)
        self.update_output_metrics(out)
        yield out

    def _reduce_kernel(self, batch: ColumnarBatch, phase: str):
        key = ("agg-reduce", phase, batch_signature(batch))

        def build():
            cap = batch.capacity
            funcs = self._funcs

            @named_jit(f"agg-reduce-{phase}")
            def kernel(columns, num_rows, mask=None):
                ctx = self._make_ctx(columns, cap, num_rows, mask)
                # no groups: one segment over every row, so each scan
                # operand is plainly reduced and the partial is the one
                # row it is, in the smallest batch there is
                actx = AggContext(jnp.zeros(cap, jnp.int32), cap,
                                  ctx.row_mask,
                                  out_capacity=MIN_CAPACITY,
                                  single_segment=True)
                if phase == "update":
                    inputs_per_f = [[e.eval(ctx) for e in bins]
                                    for bins in self._bound_inputs]
                else:
                    inputs_per_f = []
                    off = len(self._group_fields)
                    for f in funcs:
                        n = f.num_intermediates
                        inputs_per_f.append(columns[off: off + n])
                        off += n
                out_cols = []
                for outs in run_agg_phase(actx, funcs, inputs_per_f,
                                          phase):
                    out_cols.extend(outs)
                return out_cols

            return kernel

        return self.kernels.get_or_build(
            key, build,
            meta=self.kp_meta(
                f"agg-reduce-{phase}",
                members=(self._pre_stage.member_names()
                         if self._pre_stage is not None else None)))

    def _merge_reduction(self, partials, inter_schema) -> ColumnarBatch:
        merged = concat_batches(partials)
        agg = self._get_merge_exec(inter_schema)
        kern = agg._reduce_kernel(merged, "merge")
        cols = kern(merged.columns, merged.num_rows_i32)
        return ColumnarBatch(inter_schema, list(cols), 1, merged.checks)

    def _empty_partial(self, inter_schema) -> ColumnarBatch:
        from spark_rapids_tpu.columnar.batch import empty_batch
        e = empty_batch(inter_schema)
        # one row of "no inputs seen": validity false, counts zero
        return ColumnarBatch(inter_schema, e.columns, 1)


@dataclasses.dataclass(eq=False)
class GroupRef(Expression):
    """Positional reference used by the merge stage (keys are at fixed
    positions in partial batches)."""
    ordinal: int
    dtype: T.DataType

    def data_type(self, schema):
        return self.dtype

    def bind(self, schema):
        return self

    def eval(self, ctx):
        return ctx.columns[self.ordinal]



