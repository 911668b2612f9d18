"""Aggregate functions (reference `AggregateFunctions.scala`:
GpuAggregateExpression / CudfAggregate bridge; Min/Max/Sum/Count/Average/
First/Last).

TPU design: aggregation is *segment ops over sorted groups*.  The exec
sorts rows by group key, computes segment ids, and each AggregateFunction
contributes three stages mirroring the reference's update/merge/evaluate
split so partial (map-side) and final (reduce-side) aggregation distribute
exactly like Spark's:

  update(values per row)    -> per-segment intermediates   [map side]
  merge(intermediates)      -> combined intermediates      [reduce side]
  evaluate(intermediates)   -> final column

All stages are static-shape: `num_segments == capacity`, with invalid rows
routed to segment id == capacity (dropped by XLA scatter semantics).

The UNGROUPED aggregate (no group keys: `exec/aggregate._reduce_kernel`)
is the one case that is not a segment op: its context says
`single_segment`, and every operand a function registers is reduced over
the whole batch with the plain reduction of its op (a total needs no
scan), the partial emitted as the one row it is.

The grouped kernel's FEW-GROUPS body (`exec/aggregate._few_groups`) is
the other: its context says `few_groups`, the rows are not sorted,
`seg_ids` is each row's slot, and every operand a function registers is
reduced once a live slot under the slot's mask (`_reduce_slots`), with
its own op in its own dtype.  The functions are the same code in all
three; only `ScanBatch.run_round` and `_sorted_seg_sums` ask the context
how a group is reduced.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.vector import ColumnVector
from spark_rapids_tpu.exprs.base import Expression, Literal

_INT_MIN = {
    T.TypeId.INT8: -(2 ** 7), T.TypeId.INT16: -(2 ** 15),
    T.TypeId.INT32: -(2 ** 31), T.TypeId.INT64: -(2 ** 63),
    T.TypeId.DATE32: -(2 ** 31), T.TypeId.TIMESTAMP_US: -(2 ** 63),
    T.TypeId.BOOL: 0,
}
_INT_MAX = {
    T.TypeId.INT8: 2 ** 7 - 1, T.TypeId.INT16: 2 ** 15 - 1,
    T.TypeId.INT32: 2 ** 31 - 1, T.TypeId.INT64: 2 ** 63 - 1,
    T.TypeId.DATE32: 2 ** 31 - 1, T.TypeId.TIMESTAMP_US: 2 ** 63 - 1,
    T.TypeId.BOOL: 1,
}


def _segscan(combine_vals, bounds, *vals):
    """Segmented inclusive scan over rows SORTED by group (Blelchian
    flag-reset operator): the carry resets at each segment start, so
    per-group running reductions cost O(n) work and no scatter —
    XLA:TPU serializes scatters, and the binary-search (searchsorted)
    alternative measured ~300ms/call at 2M rows.

    HAND-ROLLED recursive pair-combine (NOT lax.associative_scan):
    XLA:TPU compile time for the scan HLO grows superlinearly with
    length (measured: 1.6s at 64K rows, 16.6s at 512K, minutes at 2M —
    and a [m, cap] matrix carry never finished), while this expansion
    is ~8 plain static-shape ops per level x log2(cap) levels and
    compiles in seconds at any width.  It also takes ANY number of
    value operands at no extra compile cost, where the multi-operand
    associative_scan blew up on tuple carries (the round-4 finding).

    `combine_vals(a_vals, b_vals)` combines two ADJACENT spans' value
    tuples (left, right)."""

    def rec(f, vs):
        k = f.shape[0]
        if k == 1:
            return vs
        if k % 2:
            # odd length: the appended row starts its own segment, so
            # it never contaminates a carry; sliced off on the way out
            f = jnp.concatenate([f, jnp.ones(1, f.dtype)])
            vs = tuple(jnp.concatenate([v, v[-1:]]) for v in vs)
            return tuple(v[:k] for v in rec(f, vs))
        h = k // 2
        f2 = f.reshape(h, 2)
        fa, fb = f2[:, 0], f2[:, 1]
        va = tuple(v.reshape((h, 2) + v.shape[1:])[:, 0] for v in vs)
        vb = tuple(v.reshape((h, 2) + v.shape[1:])[:, 1] for v in vs)
        merged = combine_vals(va, vb)
        v_pair = tuple(jnp.where(fb, b, m) for b, m in zip(vb, merged))
        vp = rec(fa | fb, v_pair)
        # exclusive carry into pair i = inclusive result of pair i-1
        # (pair 0 has none: masked below, the [0:1] filler is arbitrary)
        vx = tuple(jnp.concatenate([v[:1], v[:-1]]) for v in vp)
        no_carry = fa | (jnp.arange(h) == 0)
        comb_e = combine_vals(vx, va)
        out_even = tuple(jnp.where(no_carry, a, c)
                         for a, c in zip(va, comb_e))
        # interleave: out[2i] = even_i, out[2i+1] = pair-inclusive_i
        return tuple(
            jnp.stack([e, o], axis=1).reshape((k,) + e.shape[1:])
            for e, o in zip(out_even, vp))

    return rec(bounds, vals)


_SCAN_OPS = {
    "add": lambda a, b: a + b,
    "min": jnp.minimum,
    "max": jnp.maximum,
}

#: the whole-batch reduction of each scan op (single-segment contexts).
#: A sum keeps its operand's dtype, as the scan does: `jnp.sum` alone
#: would widen an int32 count to int64 before it adds (64-bit
#: elementwise is 50-100x slower on this chip)
_REDUCE_OPS = {
    "add": lambda a: jnp.sum(a, axis=0, dtype=a.dtype),
    "min": lambda a: jnp.min(a, axis=0),
    "max": lambda a: jnp.max(a, axis=0),
}


def _identity(op: str, dtype):
    """The element a reduction of `op` over no row of `dtype` gives."""
    if op == "add":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(op == "min", dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if op == "min" else info.min, dtype)


def _reduce_slots(ops, arrs, ctx: "AggContext"):
    """The few-groups body's reduction: rows stay in row order and
    `ctx.seg_ids` holds each row's slot, so every operand is reduced
    once a LIVE slot under the slot's mask, with its own reduction in
    its own dtype (a FLOAT64 sum stays float64).  One loop turn a
    group the batch really has (`ctx.num_groups`, on the device): four
    groups cost four masked passes whatever the slots' number.  Slots
    past the count keep the identity; the caller masks them."""
    k = ctx.out_capacity
    idents = [_identity(op, a.dtype) for op, a in zip(ops, arrs)]

    def one_slot(g, accs):
        mine = ctx.seg_ids == g
        out = []
        for op, a, ident, acc in zip(ops, arrs, idents, accs):
            m = mine.reshape(mine.shape + (1,) * (a.ndim - 1))
            out.append(acc.at[g].set(
                _REDUCE_OPS[op](jnp.where(m, a, ident))))
        return tuple(out)

    return jax.lax.fori_loop(
        0, ctx.num_groups, one_slot,
        tuple(jnp.full((k,) + a.shape[1:], ident, a.dtype)
              for a, ident in zip(arrs, idents)))


def _reduce_all(op: str, arr, out_capacity: int):
    """One segment covering every row: `arr` reduced over axis 0 in its
    own dtype (a FLOAT64 sum stays float64; only the order of the
    additions differs from the scan's), broadcast to the group side."""
    total = _REDUCE_OPS[op](arr)
    return jnp.broadcast_to(total, (out_capacity,) + total.shape)


class ScanBatch:
    """Cross-function segmented-scan batcher.

    Aggregate functions register per-row operands (`seg(op, arr)`) and
    the kernel runs ONE `_segscan` per round over every registered
    operand, each combined with its own op — one pass over the sorted
    rows instead of one `_segscan` PER FUNCTION (measured r4: each
    2M-row scan dispatch costs ~100ms while a stacked multi-operand
    scan runs in roughly one scan's time; a q1-shaped aggregate ran 8
    separate scans over 15 operands before this existed).

    Handles returned by `seg` resolve to per-GROUP results (gathered at
    segment ends) after `run_round()`.  Operands registered by resumed
    generators go into the next round, so a two-stage function (e.g.
    Welford m2 against the group mean) costs the whole kernel two scan
    dispatches, not two per function."""

    def __init__(self, ctx: "AggContext"):
        self._ctx = ctx
        self._ops: list = []        # combine-op name per handle
        self._pend: list = []       # (handle, row array) this round
        self._results: dict = {}    # handle -> per-group result
        # (op, id(arr)) -> (handle, arr).  The array is HELD in the
        # entry: a dedup key must not outlive its object, or a freed
        # round-1 operand's reused id() could alias a later round's
        # operand and hand it another operand's scan result.
        self._dedup: dict = {}

    def seg(self, op: str, arr) -> int:
        key = (op, id(arr))
        hit = self._dedup.get(key)
        if hit is not None:
            return hit[0]
        h = len(self._ops)
        self._ops.append(op)
        self._pend.append((h, arr))
        self._dedup[key] = (h, arr)
        return h

    def run_round(self) -> None:
        if not self._pend:
            return
        idxs = [h for h, _ in self._pend]
        arrs = [a for _, a in self._pend]
        ops = [self._ops[h] for h in idxs]
        if self._ctx.single_segment:
            # the ungrouped aggregate: a total needs no scan
            outs = [_reduce_all(op, a, self._ctx.out_capacity)
                    for op, a in zip(ops, arrs)]
        elif self._ctx.few_groups:
            # a slot a group, rows unsorted: masked reductions, no scan
            outs = _reduce_slots(ops, arrs, self._ctx)
        else:
            scan_ops = [_SCAN_OPS[op] for op in ops]

            def combine(a, b):
                return tuple(op(x, y) for op, x, y in zip(scan_ops, a, b))

            runs = _segscan(combine, self._ctx.bounds, *arrs)
            outs = [jnp.take(r, self._ctx.ends) for r in runs]
        self._results.update(zip(idxs, outs))
        self._pend = []

    def result(self, h: int):
        return self._results[h]


def _drive_eager(make_gen, ctx: "AggContext"):
    scans = ScanBatch(ctx)
    gen = make_gen(scans)
    if gen is None:
        raise NotImplementedError
    next(gen)
    while True:
        scans.run_round()
        try:
            next(gen)
        except StopIteration as e:
            return e.value


def run_agg_phase(actx: "AggContext", funcs, inputs_per_f, phase: str):
    """Drive every aggregate function's update/merge with cross-function
    scan batching; returns the per-function output tuples in order.

    Functions exposing the generator protocol (`update_scans` /
    `merge_scans` returning a generator) register their scan operands,
    yield, and resume with results once the shared round has run;
    functions without it fall back to their eager `update`/`merge`."""
    scans = ScanBatch(actx)
    slots: list = []
    live: list = []
    for f, ins in zip(funcs, inputs_per_f):
        gen = (f.update_scans(actx, scans, ins) if phase == "update"
               else f.merge_scans(actx, scans, ins))
        if gen is None:
            outs = (f.update(actx, ins) if phase == "update"
                    else f.merge(actx, ins))
            slots.append(outs)
        else:
            next(gen)
            slots.append(None)
            live.append((len(slots) - 1, gen))
    while live:
        scans.run_round()
        nxt = []
        for i, gen in live:
            try:
                next(gen)
                nxt.append((i, gen))
            except StopIteration as e:
                slots[i] = e.value
        live = nxt
    return slots


def _sorted_seg_sums(ctx: "AggContext", *vals):
    """Per-group sums of several arrays in ONE segmented scan + gathers
    at segment ends.  Additions happen in row order WITHIN each group
    only (no cross-group mixing), so float results are at least as
    deterministic as a hash groupby's, and integer wraparound matches
    Spark's non-ANSI sum.  Invalid rows must already be value-zeroed
    (they share the last group's segment id)."""
    if ctx.single_segment:
        return tuple(_reduce_all("add", v, ctx.out_capacity) for v in vals)
    if ctx.few_groups:
        return _reduce_slots(["add"] * len(vals), vals, ctx)
    runs = _segscan(lambda a, b: tuple(x + y for x, y in zip(a, b)),
                    ctx.bounds, *vals)
    return tuple(jnp.take(r, ctx.ends) for r in runs)


def _sorted_seg_sum(vals, ctx: "AggContext"):
    return _sorted_seg_sums(ctx, vals)[0]


@dataclasses.dataclass
class AggContext:
    seg_ids: jnp.ndarray     # per sorted row
    capacity: int            # row-side length (input rows)
    row_valid: jnp.ndarray   # sorted row mask
    #: True at each sorted row that STARTS a group (invalid rows never
    #: start one — they ride the last group's segment id)
    bounds: Optional[jnp.ndarray] = None
    #: per-SEGMENT index of its last sorted row (out_capacity-length;
    #: entries at or past the group count are arbitrary, must be masked)
    ends: Optional[jnp.ndarray] = None
    #: GROUP-side output length.  The exec compacts groups INSIDE the
    #: kernel (ends/outputs at the compact width) so per-group gathers
    #: and output stores never run at full row capacity — a 2M-row
    #: batch with 1K groups paid ~1/3 of its kernel time materializing
    #: full-capacity group outputs before this existed.
    out_capacity: Optional[int] = None
    #: a STATIC fact, set by the ungrouped `_reduce_kernel` and nobody
    #: else: exactly one segment covers every row (`seg_ids` all zero),
    #: so scan operands are plainly reduced and `bounds` / `ends` unused
    single_segment: bool = False
    #: a STATIC fact, set by the grouped kernel's few-groups body and
    #: nobody else: the rows are NOT sorted; `seg_ids` is each row's
    #: slot (-1: none), a slot a group in order of first appearance,
    #: `out_capacity` the number of slots and `num_groups` (a device
    #: scalar) how many of them are live.  Scan operands are reduced
    #: once a live slot under its mask (`_reduce_slots`); `bounds` /
    #: `ends` unused
    few_groups: bool = False
    num_groups: Optional[jnp.ndarray] = None

    def __post_init__(self):
        assert self.single_segment or (
            self.num_groups is not None if self.few_groups else
            self.bounds is not None and self.ends is not None), \
            "a grouped AggContext needs its bounds and ends, or its " \
            "slots' count"
        if self.out_capacity is None:
            self.out_capacity = self.capacity


class AggregateFunction:
    """One aggregate; `child` may be None for Count(*)."""
    child: Optional[Expression]

    def input_exprs(self) -> Sequence[Expression]:
        return () if self.child is None else (self.child,)

    def result_type(self, schema: T.Schema) -> T.DataType:
        raise NotImplementedError

    def intermediate_types(self, schema: T.Schema) -> Sequence[T.DataType]:
        raise NotImplementedError

    # FINAL-mode type resolution: a merge-side exec sees only the partial
    # schema (keys + intermediates), where the original input columns are
    # gone — so counts and result types must be derivable positionally.
    @property
    def num_intermediates(self) -> int:
        return 1

    def result_from_intermediates(
            self, inter: Sequence[T.DataType]) -> T.DataType:
        return inter[0]

    def update(self, ctx: AggContext, inputs: Sequence[ColumnVector]
               ) -> Sequence[ColumnVector]:
        """Eager fallback: drives this function's scan generator with a
        private ScanBatch (single-function callers; the group-by kernel
        batches across functions via run_agg_phase)."""
        return _drive_eager(
            lambda s: self.update_scans(ctx, s, inputs), ctx)

    def merge(self, ctx: AggContext, partials: Sequence[ColumnVector]
              ) -> Sequence[ColumnVector]:
        return _drive_eager(
            lambda s: self.merge_scans(ctx, s, partials), ctx)

    # batched-scan protocol (run_agg_phase): return a GENERATOR that
    # registers operands on the shared ScanBatch, yields once per scan
    # round, and `return`s the output tuple — or None to have the
    # kernel fall back to the eager update/merge above.
    def update_scans(self, ctx: AggContext, scans: "ScanBatch",
                     inputs: Sequence[ColumnVector]):
        return None

    def merge_scans(self, ctx: AggContext, scans: "ScanBatch",
                    partials: Sequence[ColumnVector]):
        return None

    def reduces_through_scans(self, inter: Sequence[T.DataType]) -> bool:
        """True when both phases drive every reduction through
        `ScanBatch.seg` (given this function's intermediate types), so
        the context alone decides HOW a group is reduced: what the
        grouped kernel's few-groups body asks of every function."""
        return True

    def evaluate(self, partials: Sequence[ColumnVector],
                 schema: T.Schema) -> ColumnVector:
        raise NotImplementedError

    def alias(self, name: str):
        return AggAlias(self, name)


@dataclasses.dataclass
class AggAlias:
    func: AggregateFunction
    name: str


def _sum_type(dt: T.DataType) -> T.DataType:
    return T.FLOAT64 if dt.is_floating else T.INT64


@dataclasses.dataclass
class Sum(AggregateFunction):
    """Spark: sum(int*) -> long, sum(float*) -> double; result is null
    only when every input in the group is null."""
    child: Expression

    def result_type(self, schema):
        return _sum_type(self.child.data_type(schema))

    def intermediate_types(self, schema):
        return (self.result_type(schema),)

    def evaluate(self, partials, schema):
        return partials[0]

    def update_scans(self, ctx, scans, inputs):
        (v,) = inputs
        dt = _sum_type(v.dtype)

        def gen():
            acc = v.data.astype(dt.storage_dtype)
            ok = v.validity & ctx.row_valid
            hs = scans.seg("add", jnp.where(ok, acc, 0))
            # count companion scans i32: it only feeds the null flag,
            # and counts are bounded by capacity < 2^31 (64-bit
            # elementwise is 50-100x slower on this chip)
            hc = scans.seg("add", ok.astype(jnp.int32))
            yield
            return (ColumnVector(dt, scans.result(hs),
                                 scans.result(hc) > 0),)
        return gen()

    def merge_scans(self, ctx, scans, partials):
        (p,) = partials

        def gen():
            ok = p.validity & ctx.row_valid
            hs = scans.seg("add", jnp.where(ok, p.data, 0))
            hc = scans.seg("add", ok.astype(jnp.int32))
            yield
            return (ColumnVector(p.dtype, scans.result(hs),
                                 scans.result(hc) > 0),)
        return gen()


@dataclasses.dataclass
class Count(AggregateFunction):
    """Count(expr) counts non-null; Count(None) == COUNT(*)."""
    child: Optional[Expression] = None

    def result_type(self, schema):
        return T.INT64

    def intermediate_types(self, schema):
        return (T.INT64,)

    def evaluate(self, partials, schema):
        return partials[0]

    def update_scans(self, ctx, scans, inputs):
        def gen():
            if self.child is None:
                ok = ctx.row_valid
            else:
                ok = inputs[0].validity & ctx.row_valid
            # i32 scan (counts bounded by capacity), widened at output
            h = scans.seg("add", ok.astype(jnp.int32))
            yield
            c = scans.result(h).astype(jnp.int64)
            return (ColumnVector(T.INT64, c,
                                 jnp.ones(ctx.out_capacity, bool)),)
        return gen()

    def merge_scans(self, ctx, scans, partials):
        (p,) = partials

        def gen():
            ok = p.validity & ctx.row_valid
            h = scans.seg("add", jnp.where(ok, p.data, 0))
            yield
            return (ColumnVector(T.INT64, scans.result(h),
                                 jnp.ones(ctx.out_capacity, bool)),)
        return gen()


def _minmax_numeric_gen(v: ColumnVector, ctx: AggContext,
                        scans: ScanBatch, is_min: bool):
    """Direct segment min/max with Spark NaN semantics (NaN is the largest
    value).  No bit-encode: 64-bit bitcasts don't lower on TPU.

    floats: max — NaN wins whenever present (map NaN -> +inf and track);
            min — NaN loses unless the whole group is NaN.

    Generator (ScanBatch protocol); yields once, returns (red, has).
    Scans run at the column's NATIVE storage width — the old int path
    widened every operand to i64, and 64-bit elementwise ops are
    50-100x slower on this chip."""
    op = "min" if is_min else "max"
    ok = v.validity & ctx.row_valid
    if v.dtype.is_floating:
        nan = jnp.isnan(v.data) & ok
        non_nan = ok & ~nan
        fill = jnp.inf if is_min else -jnp.inf
        hr = scans.seg(op, jnp.where(non_nan, v.data, fill))
        hc, hn = (scans.seg("add", x.astype(jnp.int32))
                  for x in (ok, non_nan))
        yield
        red = scans.result(hr)
        cnt, n_non_nan = scans.result(hc), scans.result(hn)
        has = cnt > 0
        if is_min:
            # all-NaN group -> NaN
            red = jnp.where(has & (n_non_nan == 0), jnp.nan, red)
        else:
            # any NaN -> NaN is the max
            red = jnp.where(cnt > n_non_nan, jnp.nan, red)
        return red.astype(v.dtype.storage_dtype), has
    fill = (_INT_MAX if is_min else _INT_MIN)[v.dtype.id]
    masked = jnp.where(ok, v.data,
                       jnp.asarray(fill, v.data.dtype))
    hr = scans.seg(op, masked)
    hh = scans.seg("add", ok.astype(jnp.int32))
    yield
    return (scans.result(hr).astype(v.dtype.storage_dtype),
            scans.result(hh) > 0)


@dataclasses.dataclass
class _MinMax(AggregateFunction):
    child: Expression

    @property
    def _is_min(self) -> bool:
        raise NotImplementedError

    def result_type(self, schema):
        return self.child.data_type(schema)

    def intermediate_types(self, schema):
        return (self.child.data_type(schema),)

    def reduces_through_scans(self, inter):
        # a string's winner comes from `_update_string`'s own lexsort
        # over the SORTED segments
        return not inter[0].is_string

    def update(self, ctx, inputs):
        (v,) = inputs
        if v.dtype.is_string:
            return self._update_string(ctx, v)
        return super().update(ctx, inputs)

    def merge(self, ctx, partials):
        return self.update(ctx, partials)

    def update_scans(self, ctx, scans, inputs):
        (v,) = inputs
        if v.dtype.is_string:
            return None

        def gen():
            red, has = yield from _minmax_numeric_gen(
                v, ctx, scans, self._is_min)
            return (ColumnVector(v.dtype, red, has),)
        return gen()

    def merge_scans(self, ctx, scans, partials):
        return self.update_scans(ctx, scans, partials)

    def evaluate(self, partials, schema):
        return partials[0]

    def _update_string(self, ctx, v: ColumnVector):
        """Strings: argmin/argmax by byte-lexicographic rank.  Lexsort
        rows by (segment, ok-last, value); each segment keeps ALL its
        rows, so the s-th distinct run in the sorted order IS segment s
        and a positional nonzero over run starts yields every segment's
        winner with no scatter (XLA:TPU serializes scatters)."""
        from spark_rapids_tpu.ops.sort_encode import (encode_key_bits,
                                                      packed_lexsort)
        cap = ctx.capacity
        ok = v.validity & ctx.row_valid
        keys = encode_key_bits(v, ascending=self._is_min,
                               nulls_first=False)
        order = packed_lexsort(
            [(ctx.seg_ids.astype(jnp.uint32), 32),
             ((~ok).astype(jnp.uint8), 1)] + keys)
        seg_sorted = jnp.take(ctx.seg_ids, order)
        isfirst = jnp.concatenate(
            [jnp.ones(1, bool), seg_sorted[1:] != seg_sorted[:-1]])
        # position of each segment's first (= winning) sorted row, in
        # segment order — every segment has >= 1 row, so run index == id
        # (group side: compact width, not row capacity)
        from spark_rapids_tpu.ops.sort_encode import masked_positions
        pos = masked_positions(isfirst, ctx.out_capacity,
                               fill_value=cap - 1)
        idx = jnp.take(order, pos).astype(jnp.int32)
        has = _sorted_seg_sum(ok.astype(jnp.int32), ctx) > 0
        # a group whose rows are all null/invalid sorted them first
        # anyway — mask it out via `has`
        out = v.gather(idx, has)
        return (out,)


class Min(_MinMax):
    _is_min = True


class Max(_MinMax):
    _is_min = False


@dataclasses.dataclass
class Average(AggregateFunction):
    """Spark avg -> double; intermediates are (sum: double, count: long)."""
    child: Expression

    def result_type(self, schema):
        return T.FLOAT64

    def intermediate_types(self, schema):
        return (T.FLOAT64, T.INT64)

    num_intermediates = 2

    def result_from_intermediates(self, inter):
        return T.FLOAT64

    def update_scans(self, ctx, scans, inputs):
        (v,) = inputs

        def gen():
            ok = v.validity & ctx.row_valid
            hs = scans.seg(
                "add", jnp.where(ok, v.data.astype(jnp.float64), 0.0))
            hc = scans.seg("add", ok.astype(jnp.int32))
            yield
            always = jnp.ones(ctx.out_capacity, bool)
            return (ColumnVector(T.FLOAT64, scans.result(hs), always),
                    ColumnVector(T.INT64,
                                 scans.result(hc).astype(jnp.int64),
                                 always))
        return gen()

    def merge_scans(self, ctx, scans, partials):
        s_p, c_p = partials

        def gen():
            ok = ctx.row_valid
            hs = scans.seg("add", jnp.where(ok, s_p.data, 0.0))
            hc = scans.seg("add", jnp.where(ok, c_p.data, 0))
            yield
            always = jnp.ones(ctx.out_capacity, bool)
            return (ColumnVector(T.FLOAT64, scans.result(hs), always),
                    ColumnVector(T.INT64, scans.result(hc), always))
        return gen()

    def evaluate(self, partials, schema):
        s, c = partials
        nonzero = c.data > 0
        avg = s.data / jnp.where(nonzero, c.data, 1).astype(jnp.float64)
        return ColumnVector(T.FLOAT64, avg, nonzero)


@dataclasses.dataclass
class _FirstLast(AggregateFunction):
    child: Expression
    ignore_nulls: bool = False

    @property
    def _is_first(self) -> bool:
        raise NotImplementedError

    def result_type(self, schema):
        return self.child.data_type(schema)

    def intermediate_types(self, schema):
        return (self.child.data_type(schema),)

    def update_scans(self, ctx, scans, inputs):
        (v,) = inputs

        def gen():
            cap = ctx.capacity
            ok = ctx.row_valid & (v.validity if self.ignore_nulls
                                  else jnp.ones(cap, bool))
            rows = jnp.arange(cap, dtype=jnp.int32)
            if self._is_first:
                hp = scans.seg("min", jnp.where(ok, rows, cap))
            else:
                hp = scans.seg("max", jnp.where(ok, rows, -1))
            hh = scans.seg("add", ok.astype(jnp.int32))
            yield
            has = scans.result(hh) > 0
            idx = jnp.where(has, scans.result(hp), 0).astype(jnp.int32)
            return (v.gather(idx, has),)
        return gen()

    def merge_scans(self, ctx, scans, partials):
        return self.update_scans(ctx, scans, partials)

    def evaluate(self, partials, schema):
        return partials[0]


class First(_FirstLast):
    _is_first = True


class Last(_FirstLast):
    _is_first = False


def Avg(e: Expression) -> Average:
    return Average(e)


def CountStar() -> Count:
    return Count(None)


@dataclasses.dataclass
class VarianceSamp(AggregateFunction):
    """Spark var_samp -> double; intermediates (count, mean, m2) with a
    Welford/Chan-style merge — the same buffer layout as Spark's
    CentralMomentAgg, and numerically stable where raw (sum, sum_sq)
    intermediates cancel catastrophically (large-magnitude low-variance
    data, e.g. values ~1e8).  Null for groups with fewer than two
    non-null inputs (pandas ddof=1 semantics; reference registers
    GpuStddevSamp-family aggregates over cuDF VARIANCE/STD)."""
    child: Expression

    def result_type(self, schema):
        return T.FLOAT64

    def intermediate_types(self, schema):
        return (T.INT64, T.FLOAT64, T.FLOAT64)

    num_intermediates = 3

    def result_from_intermediates(self, inter):
        return T.FLOAT64

    def update_scans(self, ctx, scans, inputs):
        (v,) = inputs

        def gen():
            ok = v.validity & ctx.row_valid
            x = jnp.where(ok, v.data.astype(jnp.float64), 0.0)
            hs = scans.seg("add", x)
            hc = scans.seg("add", ok.astype(jnp.int32))
            yield
            c = scans.result(hc).astype(jnp.int64)
            mean = scans.result(hs) / \
                jnp.maximum(c, 1).astype(jnp.float64)
            # second round against the group mean: m2 = sum((x-mean)^2)
            d = jnp.where(ok, x - jnp.take(mean, ctx.seg_ids), 0.0)
            hm = scans.seg("add", d * d)
            yield
            always = jnp.ones(ctx.out_capacity, bool)
            return (ColumnVector(T.INT64, c, always),
                    ColumnVector(T.FLOAT64, mean, always),
                    ColumnVector(T.FLOAT64, scans.result(hm), always))
        return gen()

    def merge_scans(self, ctx, scans, partials):
        c_p, mean_p, m2_p = partials

        def gen():
            ok = ctx.row_valid
            cr = jnp.where(ok, c_p.data, 0)
            crf = cr.astype(jnp.float64)
            hc = scans.seg("add", cr)
            hs = scans.seg("add", jnp.where(ok, mean_p.data * crf, 0.0))
            yield
            c = scans.result(hc)
            mean = scans.result(hs) / \
                jnp.maximum(c, 1).astype(jnp.float64)
            # Chan's merge: m2 = sum_i(m2_i + c_i*(mean_i - mean)^2)
            delta = mean_p.data - jnp.take(mean, ctx.seg_ids)
            contrib = jnp.where(ok, m2_p.data + crf * delta * delta, 0.0)
            hm = scans.seg("add", contrib)
            yield
            always = jnp.ones(ctx.out_capacity, bool)
            return (ColumnVector(T.INT64, c, always),
                    ColumnVector(T.FLOAT64, mean, always),
                    ColumnVector(T.FLOAT64, scans.result(hm), always))
        return gen()

    def _var(self, partials):
        c, _mean, m2 = partials
        ok = c.data > 1
        denom = jnp.where(ok, c.data.astype(jnp.float64) - 1.0, 1.0)
        return m2.data / denom, ok

    def evaluate(self, partials, schema):
        var, ok = self._var(partials)
        return ColumnVector(T.FLOAT64, var, ok)


@dataclasses.dataclass
class StddevSamp(VarianceSamp):
    """Spark stddev_samp -> double (sqrt of the sample variance)."""

    def evaluate(self, partials, schema):
        var, ok = self._var(partials)
        return ColumnVector(T.FLOAT64, jnp.sqrt(var), ok)
