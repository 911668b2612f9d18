"""Window operator (reference `GpuWindowExec.scala:99,177` +
`GpuWindowExpression.scala`: rows-frames, range-frames-on-timestamp,
row_number, min/max/sum/count/avg window functions).

TPU design: one jitted kernel per batch sorts rows by (partition keys,
order keys), computes partition segments, evaluates every window function
over the sorted layout, then scatters results back to the original row
order (Spark preserves input order semantics only per-partition; we
restore the exact input order).

Frame math is all O(n) or O(n log n) vectorized:
  - running (UNBOUNDED PRECEDING..CURRENT): segment-local cumulative ops
    via global cumsum minus segment-start offset;
  - whole-partition (UNBOUNDED..UNBOUNDED): segment reduce + gather;
  - sliding rows-frames: prefix-sum differences with bounds clamped to
    the segment;
  - range frames: vectorized binary search (log2(cap) steps) over the
    (segment, order-value) lexicographic order.

The exec requires its child coalesced to a single batch per partition
group (RequireSingleBatch), the same contract as the reference.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, Optional, Sequence

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import ColumnVector
from spark_rapids_tpu.exec.base import (
    CoalesceGoal, RequireSingleBatch, TpuExec, UnaryExecBase,
    batch_signature, make_eval_context, named_jit)
from spark_rapids_tpu.exec.sort import SortOrder
from spark_rapids_tpu.exprs.base import Expression, output_name
from spark_rapids_tpu.ops.sort_encode import (
    hash_prefix_sort_bounds, sort_with_bounds, wide_key_set)
from spark_rapids_tpu.utils import checks as CK
from spark_rapids_tpu.utils import metrics as M

UNBOUNDED = None
CURRENT_ROW = 0


@dataclasses.dataclass(frozen=True)
class WindowFrame:
    """rows/range frame; bounds: None = unbounded, int offsets otherwise
    (negative = preceding, positive = following, 0 = current row)."""
    is_rows: bool = True
    lower: Optional[int] = UNBOUNDED   # default UNBOUNDED PRECEDING
    upper: Optional[int] = CURRENT_ROW  # default CURRENT ROW


@dataclasses.dataclass
class WindowSpec:
    partition_by: Sequence[Expression]
    order_by: Sequence[SortOrder] = ()
    frame: WindowFrame = WindowFrame()


@dataclasses.dataclass
class WindowFunction:
    kind: str                      # row_number, rank, dense_rank, lead,
    # lag, sum, min, max, count, avg, first, last
    child: Optional[Expression] = None
    offset: int = 1                # for lead/lag
    default: Optional[object] = None

    def alias(self, name):
        return (self, name)


def RowNumber():
    return WindowFunction("row_number")


def Rank():
    return WindowFunction("rank")


def DenseRank():
    return WindowFunction("dense_rank")


def Lead(e, offset=1, default=None):
    return WindowFunction("lead", e, offset, default)


def Lag(e, offset=1, default=None):
    return WindowFunction("lag", e, offset, default)


def WinSum(e):
    return WindowFunction("sum", e)


def WinMin(e):
    return WindowFunction("min", e)


def WinMax(e):
    return WindowFunction("max", e)


def WinCount(e):
    return WindowFunction("count", e)


def WinAvg(e):
    return WindowFunction("avg", e)


def _result_type(fn: WindowFunction, schema) -> T.DataType:
    if fn.kind in ("row_number", "rank", "dense_rank"):
        return T.INT32
    if fn.kind == "count":
        return T.INT64
    if fn.kind == "avg":
        return T.FLOAT64
    dt = fn.child.data_type(schema)
    if fn.kind == "sum":
        return T.FLOAT64 if dt.is_floating else T.INT64
    return dt


def _lex_searchsorted(seg, vals, q_seg, q_vals, side: str, cap: int):
    """Vectorized binary search over rows sorted by (seg, vals):
    first index where (seg, vals) >/>= (q_seg, q_vals)."""
    lo = jnp.zeros(q_seg.shape, jnp.int32)
    hi = jnp.full(q_seg.shape, cap, jnp.int32)
    steps = max(1, math.ceil(math.log2(max(cap, 2))) + 1)
    for _ in range(steps):
        mid = (lo + hi) // 2
        ms = jnp.take(seg, mid, mode="clip")
        mv = jnp.take(vals, mid, mode="clip")
        if side == "left":
            go_right = (ms < q_seg) | ((ms == q_seg) & (mv < q_vals))
        else:
            go_right = (ms < q_seg) | ((ms == q_seg) & (mv <= q_vals))
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    return lo


class WindowExec(UnaryExecBase):
    def __init__(self, window_exprs: Sequence, spec: WindowSpec,
                 child: TpuExec):
        """window_exprs: list of (WindowFunction, name) or WindowFunction."""
        super().__init__(child)
        self.spec = spec
        self.fns = []
        child_schema = child.output_schema()
        self._child_schema = child_schema
        names = []
        for i, w in enumerate(window_exprs):
            fn, name = w if isinstance(w, tuple) else (w, f"w{i}")
            self.fns.append(fn)
            names.append(name)
        self._bound_parts = [e.bind(child_schema)
                             for e in spec.partition_by]
        self._bound_order = [
            SortOrder(o.expr.bind(child_schema), o.ascending,
                      o.nulls_first) for o in spec.order_by]
        self._bound_inputs = [
            fn.child.bind(child_schema) if fn.child is not None else None
            for fn in self.fns]
        fields = list(child_schema.fields) + [
            T.Field(n, _result_type(fn, child_schema))
            for fn, n in zip(self.fns, names)]
        self._schema = T.Schema(tuple(fields))

    def output_schema(self):
        return self._schema

    def children_coalesce_goal(self) -> list[Optional[CoalesceGoal]]:
        return [RequireSingleBatch()]

    def describe(self):
        return (f"WindowExec([{', '.join(f.kind for f in self.fns)}], "
                f"partitionBy={len(self.spec.partition_by)})")

    def cache_scope(self):
        from spark_rapids_tpu.exprs.base import fingerprint
        return (fingerprint(self.spec), fingerprint(self._bound_parts),
                fingerprint(self._bound_order),
                fingerprint(self._bound_inputs), fingerprint(self.fns))

    # ------------------------------------------------------------------
    def _use_hash_partitions(self, batch: ColumnarBatch) -> bool:
        """Wide PARTITION BY key sets (string partitions explode into
        one 9-bit sort key per char position) sort by two murmur3
        words instead — partition order is unobservable in window
        results, only the grouping and the ORDER BY within it matter.
        Same retry/deopt contract as the aggregate's hash lane."""
        if not self._bound_parts or CK.is_retrying() or \
                getattr(self, "_hash_parts_disabled", False):
            return False
        from spark_rapids_tpu import config as C
        if not C.get_active_conf()[C.HASH_GROUPING_ENABLED]:
            return False
        return wide_key_set(self._bound_parts, batch, self._child_schema)

    def _disable_hash_partitions(self) -> None:
        self._hash_parts_disabled = True

    def _kernel(self, batch: ColumnarBatch):
        use_hash = self._use_hash_partitions(batch)
        key = ("window", use_hash, batch_signature(batch))

        def build():
            cap = batch.capacity
            frame = self.spec.frame

            @named_jit("window")
            def kernel(columns, num_rows):
                ctx = make_eval_context(columns, cap, num_rows)
                parts = [e.eval(ctx) for e in self._bound_parts]
                orders = [o.expr.eval(ctx) for o in self._bound_order]
                okeys = [(o, so.ascending, so.resolved_nulls_first)
                         for o, so in zip(orders, self._bound_order)]
                if use_hash:
                    perm, sorted_mask, pbounds, obounds_all, collision = \
                        hash_prefix_sort_bounds(parts, okeys,
                                                ctx.row_mask)
                else:
                    keyspec = [(p, True, True) for p in parts] + okeys
                    perm, sorted_mask, pbounds, obounds_all = \
                        sort_with_bounds(keyspec, ctx.row_mask,
                                         prefix=len(parts))
                    collision = None
                # partition segments (partition keys only)
                if parts:
                    bounds = pbounds
                else:
                    bounds = (jnp.arange(cap) == 0) & sorted_mask
                seg = jnp.cumsum(bounds.astype(jnp.int32)) - 1
                seg = jnp.where(sorted_mask, seg, cap)
                pos = jnp.arange(cap, dtype=jnp.int32)
                (seg_start_idx,) = jnp.nonzero(bounds, size=cap,
                                               fill_value=cap - 1)
                seg_start = jnp.take(seg_start_idx,
                                     jnp.clip(seg, 0, cap - 1))
                # per-segment exclusive end WITHOUT a scatter (XLA:TPU
                # serializes segment_sum): rows are partition-sorted
                # with invalid rows last, so segment s ends where s+1
                # starts, and the LAST segment ends at num_rows
                num_segs = bounds.sum().astype(jnp.int32)
                nxt = jnp.concatenate(
                    [seg_start_idx[1:],
                     jnp.full((1,), cap, seg_start_idx.dtype)])
                seg_end_by_id = jnp.where(
                    jnp.arange(cap) >= num_segs - 1,
                    jnp.asarray(num_rows, jnp.int32), nxt.astype(jnp.int32))
                seg_end = jnp.take(seg_end_by_id,
                                   jnp.clip(seg, 0, cap - 1))  # exclusive

                # order-key change flags (for rank/dense_rank)
                obounds = obounds_all if orders else bounds

                # frame bounds [lo, hi) per row, shared by all functions
                if frame.is_rows:
                    lo = seg_start if frame.lower is None else \
                        jnp.maximum(pos + frame.lower, seg_start)
                    hi = seg_end if frame.upper is None else \
                        jnp.minimum(pos + frame.upper + 1, seg_end)
                    hi = jnp.maximum(hi, lo)
                else:
                    # RANGE frame: single integer/date/timestamp order key
                    assert len(orders) == 1, \
                        "range frames need exactly one order key"
                    oc = orders[0].gather(perm, sorted_mask)
                    ovals = oc.data.astype(jnp.int64)
                    seg_q = jnp.where(sorted_mask, seg, cap)
                    if frame.lower is None:
                        lo = seg_start
                    else:
                        lo = _lex_searchsorted(
                            seg_q, ovals, seg_q, ovals + frame.lower,
                            "left", cap).astype(jnp.int32)
                        lo = jnp.maximum(lo, seg_start)
                    if frame.upper is None:
                        hi = seg_end
                    else:
                        hi = _lex_searchsorted(
                            seg_q, ovals, seg_q, ovals + frame.upper,
                            "right", cap).astype(jnp.int32)
                        hi = jnp.minimum(hi, seg_end)
                    hi = jnp.maximum(hi, lo)

                results = []
                for fn, bin_ in zip(self.fns, self._bound_inputs):
                    if bin_ is not None:
                        v = bin_.eval(ctx)
                        sv = v.gather(perm, sorted_mask)
                    else:
                        sv = None
                    results.append(self._eval_fn(
                        fn, sv, pos, seg, seg_start, seg_end, obounds,
                        sorted_mask, cap, lo, hi))

                # scatter back to original row order
                inv = jnp.zeros(cap, jnp.int32).at[perm].set(
                    pos, mode="drop")
                out = []
                for r in results:
                    out.append(r.gather(inv, ctx.row_mask))
                return list(columns) + out, collision

            return kernel

        return self.kernels.get_or_build(
            key, build, meta=self.kp_meta("window"))

    def _eval_fn(self, fn, sv, pos, seg, seg_start, seg_end, obounds,
                 sorted_mask, cap, lo, hi) -> ColumnVector:
        k = fn.kind
        if k == "row_number":
            data = (pos - seg_start + 1).astype(jnp.int32)
            return ColumnVector(T.INT32, data, sorted_mask)
        if k in ("rank", "dense_rank"):
            # dense: count of order-changes within segment up to row
            ochange = obounds.astype(jnp.int32)
            cum_o = jnp.cumsum(ochange)
            start_o = jnp.take(cum_o, seg_start)
            dense = cum_o - start_o + 1
            if k == "dense_rank":
                return ColumnVector(T.INT32, dense.astype(jnp.int32),
                                    sorted_mask)
            # rank: position of first row of the tie group
            (grp_first,) = jnp.nonzero(obounds, size=cap,
                                       fill_value=cap - 1)
            tie_start = jnp.take(grp_first,
                                 jnp.clip(cum_o - 1, 0, cap - 1))
            data = (tie_start - seg_start + 1).astype(jnp.int32)
            return ColumnVector(T.INT32, data, sorted_mask)
        if k in ("lead", "lag"):
            off = fn.offset if k == "lead" else -fn.offset
            src = pos + off
            in_seg = (src >= seg_start) & (src < seg_end)
            got = sv.gather(jnp.clip(src, 0, cap - 1), in_seg & sorted_mask)
            if fn.default is not None:
                from spark_rapids_tpu.exprs.base import Literal
                # fill out-of-frame with the default literal
                dv = Literal.of(fn.default)
                dctx = make_eval_context([], cap, jnp.int32(cap))
                dcol = dv.eval(dctx)
                from spark_rapids_tpu.exprs.conditional import _select
                got = _select(in_seg, got, dcol)
                got = ColumnVector(got.dtype, got.data,
                                   jnp.where(in_seg, got.validity,
                                             sorted_mask), got.lengths)
            return got

        # frame-aggregates ------------------------------------------------
        ok = sv.validity & sorted_mask
        if k == "count":
            c = ok.astype(jnp.int64)
            ps = jnp.cumsum(c)
            total = _range_sum(ps, lo, hi)
            return ColumnVector(T.INT64, total, sorted_mask)
        if k in ("sum", "avg"):
            acc_t = jnp.float64 if (sv.dtype.is_floating or k == "avg") \
                else jnp.int64
            vals = jnp.where(ok, sv.data.astype(acc_t), 0)
            ps = jnp.cumsum(vals)
            s = _range_sum(ps, lo, hi)
            cnt = _range_sum(jnp.cumsum(ok.astype(jnp.int64)), lo, hi)
            if k == "sum":
                dt = T.FLOAT64 if sv.dtype.is_floating else T.INT64
                return ColumnVector(dt, s.astype(dt.storage_dtype),
                                    sorted_mask & (cnt > 0))
            avg = s.astype(jnp.float64) / jnp.where(cnt > 0, cnt, 1)
            return ColumnVector(T.FLOAT64, avg, sorted_mask & (cnt > 0))
        if k in ("min", "max"):
            return self._minmax_frame(sv, ok, lo, hi, cap, k == "min",
                                      sorted_mask)
        if k in ("first", "last"):
            idx = lo if k == "first" else hi - 1
            got = sv.gather(jnp.clip(idx, 0, cap - 1),
                            sorted_mask & (hi > lo))
            return got
        raise ValueError(f"unsupported window function {k}")

    def _minmax_frame(self, sv, ok, lo, hi, cap, is_min, sorted_mask):
        """Sliding min/max via sparse segment-tree style prefix tables:
        O(n log n) doubling table (sparse table RMQ)."""
        if sv.dtype.is_string:
            raise NotImplementedError("string window min/max")
        if sv.dtype.is_floating:
            fill = jnp.inf if is_min else -jnp.inf
            vals = jnp.where(ok, sv.data.astype(jnp.float64), fill)
        else:
            info = jnp.iinfo(jnp.int64)
            fill = info.max if is_min else info.min
            vals = jnp.where(ok, sv.data.astype(jnp.int64), fill)
        levels = [vals]
        span = 1
        while span < cap:
            prev = levels[-1]
            shifted = jnp.roll(prev, -span)
            pad_fill = jnp.asarray(fill, prev.dtype)
            shifted = jnp.where(jnp.arange(cap) + span < cap, shifted,
                                pad_fill)
            levels.append(jnp.minimum(prev, shifted) if is_min
                          else jnp.maximum(prev, shifted))
            span *= 2
        # RMQ query [lo, hi): k = floor(log2(hi-lo))
        length = jnp.maximum(hi - lo, 1)
        k = (jnp.log2(length.astype(jnp.float64))).astype(jnp.int32)
        k = jnp.clip(k, 0, len(levels) - 1)
        stacked = jnp.stack(levels)  # [L, cap]
        a = stacked[k, jnp.clip(lo, 0, cap - 1)]
        b_idx = jnp.clip(hi - (1 << k.astype(jnp.int64)), 0, cap - 1)
        b = stacked[k, b_idx]
        red = jnp.minimum(a, b) if is_min else jnp.maximum(a, b)
        has = hi > lo
        # count valid in range to set validity
        cnt = _range_sum(jnp.cumsum(ok.astype(jnp.int64)), lo, hi)
        return ColumnVector(sv.dtype, red.astype(sv.dtype.storage_dtype),
                            sorted_mask & has & (cnt > 0))

    def _window_one(self, batch: ColumnarBatch) -> ColumnarBatch:
        with self.metrics.timed(M.TOTAL_TIME):
            kern = self._kernel(batch)
            cols, coll = kern(batch.columns, batch.num_rows_i32)
            checks = CK.register_deopt(
                coll, f"hashWindowParts[exec {self.exec_id}]",
                self._disable_hash_partitions, batch.checks)
            return ColumnarBatch(self._schema, list(cols),
                                 batch._rows, checks)

    def process_partition(self, batches) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.exec.coalesce import coalesce_iterator
        batches = coalesce_iterator(batches, RequireSingleBatch(),
                                    self._child_schema, self.metrics)
        for batch in batches:
            batch = batch.dense()
            # window frames read the WHOLE partition group
            # (RequireSingleBatch contract) — a row split would cut
            # partitions mid-frame, so pressure here takes the no-split
            # lane: spill + retry in place, floor fallback past that
            (out,) = tuple(self.oom_retry_batches(
                batch, self._window_one, split=False,
                label=self.name()))
            self.update_output_metrics(out)
            yield out


def _range_sum(prefix, lo, hi):
    """sum over [lo, hi) given inclusive prefix sums."""
    cap = prefix.shape[0]
    hi_v = jnp.where(hi > 0, jnp.take(prefix, jnp.clip(hi - 1, 0, cap - 1)),
                     0)
    lo_v = jnp.where(lo > 0, jnp.take(prefix, jnp.clip(lo - 1, 0, cap - 1)),
                     0)
    return hi_v - lo_v


# ---------------------------------------------------------------------------
# Planner-facing window node + independent CPU evaluation (the golden
# engine for window parity tests; Spark's WindowExec analog on the
# fallback side).  The override rule in plan/overrides.py converts it to
# the TPU WindowExec above.
from spark_rapids_tpu.plan.nodes import CpuNode as _CpuNode


class CpuWindow(_CpuNode):
    """CPU plan node: child columns + one column per window function."""

    def __init__(self, window_exprs: Sequence, spec: WindowSpec, child):
        super().__init__(child)
        self.spec = spec
        self.window_exprs = [
            w if isinstance(w, tuple) else (w, f"w{i}")
            for i, w in enumerate(window_exprs)]
        cs = child.output_schema()
        fields = list(cs.fields) + [
            T.Field(n, _result_type(fn, cs))
            for fn, n in self.window_exprs]
        self._schema = T.Schema(tuple(fields))

    def output_schema(self):
        return self._schema

    def output_partition_count(self) -> int:
        return 1

    def name(self) -> str:
        return "CpuWindow"

    def describe(self) -> str:
        return (f"CpuWindow([{', '.join(f.kind for f, _ in self.window_exprs)}]"
                f", partitionBy={len(self.spec.partition_by)})")

    def execute(self):
        import pandas as pd
        from spark_rapids_tpu.plan.nodes import empty_df, normalize_df
        parts = [df for it in self.child.execute() for df in it]
        cs = self.child.output_schema()
        df = (pd.concat(parts, ignore_index=True) if parts
              else empty_df(cs))
        out = _cpu_window_eval(df, cs, self.spec, self.window_exprs)
        return [iter([normalize_df(out, self._schema)])]


def _cpu_window_eval(df, child_schema, spec: WindowSpec, window_exprs):
    """Row-at-a-time reference implementation of the window semantics
    the TPU kernel vectorizes: per-partition sorted evaluation with
    rows/range frames (range CURRENT ROW includes peers, like Spark)."""
    import numpy as np
    import pandas as pd
    from spark_rapids_tpu.plan.cpu_eval import cpu_eval, nullable_dtype

    n = len(df)
    out = df.copy()
    results = {name: [None] * n for _, name in window_exprs}
    if n == 0:
        for fn, name in window_exprs:
            out[name] = pd.Series(
                [], dtype=nullable_dtype(_result_type(fn, child_schema)))
        return out

    pcols = [cpu_eval(e, df, child_schema) for e in spec.partition_by]
    ocols = [cpu_eval(o.expr, df, child_schema) for o in spec.order_by]

    def okey(i):
        key = []
        for s, o in zip(ocols, spec.order_by):
            v = s.iloc[i]
            null = pd.isna(v)
            # null ordering then direction, mirroring SortOrder's
            # resolved default (asc -> nulls first, desc -> nulls last)
            key.append((null != o.resolved_nulls_first,
                        _dirval(v, o.ascending, null)))
        return tuple(key)

    def pkey(i):
        return tuple(None if pd.isna(s.iloc[i]) else s.iloc[i]
                     for s in pcols)

    groups: dict = {}
    for i in range(n):
        groups.setdefault(pkey(i), []).append(i)

    frame = spec.frame
    fn_inputs = {name: (cpu_eval(fn.child, df, child_schema)
                        if fn.child is not None else None)
                 for fn, name in window_exprs}
    for rows in groups.values():
        rows.sort(key=okey)
        m = len(rows)
        order_vals = [okey(i) for i in rows]
        for fn, name in window_exprs:
            vals = fn_inputs[name]
            res = results[name]
            if fn.kind == "row_number":
                for pos, i in enumerate(rows):
                    res[i] = pos + 1
            elif fn.kind in ("rank", "dense_rank"):
                rank = dense = 0
                prev = object()
                for pos, i in enumerate(rows):
                    if order_vals[pos] != prev:
                        rank = pos + 1
                        dense += 1
                        prev = order_vals[pos]
                    res[i] = rank if fn.kind == "rank" else dense
            elif fn.kind in ("lead", "lag"):
                step = fn.offset if fn.kind == "lead" else -fn.offset
                for pos, i in enumerate(rows):
                    j = pos + step
                    if 0 <= j < m:
                        v = vals.iloc[rows[j]]
                        res[i] = None if pd.isna(v) else v
                    else:
                        res[i] = fn.default
            else:  # framed aggregates
                for pos, i in enumerate(rows):
                    lo, hi = _frame_bounds(frame, pos, m, order_vals)
                    window = [vals.iloc[rows[j]]
                              for j in range(lo, hi + 1)]
                    res[i] = _frame_agg(fn.kind, window)

    for fn, name in window_exprs:
        out[name] = pd.Series(results[name]).astype(
            nullable_dtype(_result_type(fn, child_schema)))
    return out


def _dirval(v, ascending: bool, null: bool):
    if null:
        return 0
    if ascending:
        return v
    if isinstance(v, str):
        # descending strings: inverted bytes + a terminator sentinel
        # larger than any inverted byte, so a prefix sorts AFTER its
        # extensions ("ab" before "a" descending)
        return tuple(255 - b for b in v.encode("utf-8")) + (256,)
    return -v


def _frame_bounds(frame: WindowFrame, pos: int, m: int, order_vals):
    if frame.is_rows:
        lo = 0 if frame.lower is None else max(0, pos + frame.lower)
        hi = m - 1 if frame.upper is None else min(m - 1,
                                                   pos + frame.upper)
        return lo, min(hi, m - 1)
    # range frame with UNBOUNDED / CURRENT ROW bounds: peers included
    if frame.lower is None:
        lo = 0
    elif frame.lower == 0:
        lo = pos
        while lo > 0 and order_vals[lo - 1] == order_vals[pos]:
            lo -= 1
    else:
        raise NotImplementedError(
            "CPU range frames support UNBOUNDED/CURRENT bounds")
    if frame.upper is None:
        hi = m - 1
    elif frame.upper == 0:
        hi = pos
        while hi < m - 1 and order_vals[hi + 1] == order_vals[pos]:
            hi += 1
    else:
        raise NotImplementedError(
            "CPU range frames support UNBOUNDED/CURRENT bounds")
    return lo, hi


def _frame_agg(kind: str, window: list):
    """`window` holds raw frame values INCLUDING nulls: first/last keep
    Spark's ignoreNulls=false semantics (a null boundary row yields
    null), the others skip nulls like their aggregate counterparts."""
    import pandas as pd
    if kind == "first":
        v = window[0] if window else None
        return None if v is None or pd.isna(v) else v
    if kind == "last":
        v = window[-1] if window else None
        return None if v is None or pd.isna(v) else v
    vals = [v for v in window if not pd.isna(v)]
    if kind == "count":
        return len(vals)
    if not vals:
        return None
    if kind == "sum":
        return sum(vals)
    if kind == "min":
        return min(vals)
    if kind == "max":
        return max(vals)
    if kind == "avg":
        return sum(vals) / len(vals)
    raise NotImplementedError(f"window agg {kind}")
