"""TPU partitioners (reference `GpuHashPartitioning.scala`,
`GpuRoundRobinPartitioning.scala`, `GpuSinglePartitioning.scala`,
`GpuRangePartitioner.scala` + `GpuPartitioning.scala` contiguous split).

Each partitioner computes per-row target partition ids on device, then
`contiguous_split` stably reorders rows by partition and returns per-
partition slices — the analog of cuDF's `Table.contiguousSplit` after a
murmur3 partition kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import bucket_capacity
from spark_rapids_tpu.exec.base import KernelCache, batch_signature, \
    columns_signature, make_eval_context, named_jit
from spark_rapids_tpu.exprs.base import Expression
from spark_rapids_tpu.ops.murmur3 import partition_ids
from spark_rapids_tpu.ops.sort_encode import multi_key_argsort


class TpuPartitioning:
    num_partitions: int

    def bind(self, schema: T.Schema) -> "TpuPartitioning":
        return self

    def partition_batch(self, batch: ColumnarBatch
                        ) -> list[ColumnarBatch]:
        """Split a batch into num_partitions batches (possibly empty)."""
        raise NotImplementedError


def _split_kernel_for(cache: KernelCache, batch: ColumnarBatch,
                      pid_fn, num_partitions: int, extra_key=()):
    """Shared: sort rows by partition id, count per partition.  `pid_fn`
    receives a traced `extra` pytree (e.g. range bounds) so data-dependent
    parameters stay kernel ARGUMENTS — one compile serves any bounds."""
    key = ("split", num_partitions, extra_key, batch_signature(batch))

    def build():
        cap = batch.capacity

        @named_jit("exchange-split")
        def kernel(columns, num_rows, salt, extra, mask=None):
            ctx = make_eval_context(columns, cap, num_rows, mask)
            pids = pid_fn(ctx, salt, extra)
            pids = jnp.where(ctx.row_mask, pids, num_partitions)
            cols, counts = _pid_sort_reorder(
                pids, columns, ctx.row_mask, num_partitions)
            return cols, counts

        return kernel

    return cache.get_or_build(key, build)


def _pid_sort_reorder(pids, columns, row_mask, npart: int):
    """Stable partition reorder by pid: ONE (pid, iota) sort, then the
    columns follow through `_gather_reordered` (a few stacked gathers).

    The sort network carries nothing but the iota.  The columns used to
    ride it as payload operands, and XLA:TPU variadic-sort COMPILE time
    grows steeply with operand count past ~16K rows: compiled for a
    described v5e (host time, no chip), a stable 64K-row sort took 28 s
    with 2 operands, 78 s with 5, 185 s with 9, and the 13-operand split
    of TPC-H q3's lineitem 514 s — one kernel, per batch shape (this
    form: 14.7 s).  Whether payload operands beat the gathers at run
    time: not measured on the current machine.
    Returns (reordered ColumnVectors, per-partition counts)."""
    from jax import lax
    cap = pids.shape[0]
    # counts via one-hot reduce (bincount lowers to a serialized
    # scatter-add on XLA:TPU)
    counts = (pids[:, None] ==
              jnp.arange(npart, dtype=pids.dtype)[None, :]
              ).astype(jnp.int32).sum(axis=0)
    # iota as the last key of an unstable sort == the stable order,
    # without the tie-break operand a stable sort adds
    _, order = lax.sort([pids.astype(jnp.uint32),
                         lax.iota(jnp.int32, cap)],
                        num_keys=2, is_stable=False)
    valid = jnp.take(row_mask, order)
    return _gather_reordered(columns, order, valid), counts


def _gather_reordered(columns, order, valid, packed_bits=None):
    """Row reorder with the fewest random-access streams (each costs
    ~70ns/row on this chip, dwarfing bandwidth): all 4-byte value
    streams AND the packed validity word ride ONE stacked gather, f64
    streams another (`gather_columns_grouped`).  Strings keep the
    general ColumnVector.gather (char tensors need their own streams
    anyway).  `packed_bits` lets a caller that gathers the same
    columns repeatedly (the partition cut kernel) pack the validity
    mask once."""
    from spark_rapids_tpu.columnar.vector import gather_columns_grouped
    return gather_columns_grouped(columns, order, valid, packed_bits)


#: lazy slicing keeps slices at the INPUT batch's capacity (the count is
#: still on device), so it only pays off when that capacity is small;
#: past this cap the count sync (0.4 ms on an idle v5e, PERF.md PR 30;
#: on a busy one the wait for everything queued before it) amortizes
#: over real compute and tightly-bucketed slices matter more than the
#: round trip.  A consumer that needs its input whole (a join's build
#: side) asks for the counts of many such slices in one read.
LAZY_SLICE_MAX_CAP = 1 << 16


_CUT_CACHE = KernelCache(("partition_cut",))


def _cut_kernel_for(schema: T.Schema, cols, total_cap: int, n_parts: int):
    """ONE jitted dispatch that cuts the pid-sorted batch into all
    n_parts full-capacity slices (plus their lazy row counts).  The
    per-partition lazy-slice loop this replaces paid ~6 eager
    dispatches per COLUMN per partition — on a deep plan (TPC-DS q64:
    18 joins, ~30 exchanges) that dominated wall-clock; here XLA fuses
    the whole cut and the engine pays one dispatch per input batch."""
    key = (total_cap, n_parts) + columns_signature(schema.fields, cols)

    def build():
        from spark_rapids_tpu.columnar.vector import pack_validity_bits
        base = jnp.arange(total_cap)

        @named_jit("exchange-cut")
        def kernel(columns, counts):
            offs = jnp.cumsum(counts) - counts
            packed_bits = pack_validity_bits(columns)
            outs = []
            for p in range(n_parts):
                valid = base < counts[p]
                idx = jnp.where(valid, base + offs[p], 0)
                outs.append((_gather_reordered(columns, idx, valid,
                                               packed_bits),
                             counts[p].astype(jnp.int32)))
            return outs

        return kernel

    return _CUT_CACHE.get_or_build(key, build)


def _slice_partitions(batch_cols, counts, schema: T.Schema,
                      total_cap: int, checks: tuple = ()
                      ) -> list[ColumnarBatch]:
    """Cut the pid-sorted batch into per-partition batches.  `counts`
    may be a DEVICE vector: small batches slice sync-free (one fused
    cut kernel, lazy row counts); large ones sync once and cut tight
    host-side slices.  (Lazy slicing at ANY capacity for
    clustering-only consumers was tried and measured SLOWER — the
    full-capacity slices make every downstream per-slice kernel pay the
    input capacity, which costs more than the count sync saves.)"""
    n_parts = counts.shape[0]
    if not isinstance(counts, np.ndarray) and total_cap <= LAZY_SLICE_MAX_CAP:
        kern = _cut_kernel_for(schema, batch_cols, total_cap, n_parts)
        return [ColumnarBatch(schema, cols, n, checks)
                for cols, n in kern(list(batch_cols), counts)]
    if not isinstance(counts, np.ndarray):
        from spark_rapids_tpu.utils import checks as CK
        CK.note_host_sync("partition.cut", nbytes=4 * n_parts)
    counts = np.asarray(counts)
    out = []
    offsets = np.concatenate([[0], np.cumsum(counts)])
    reordered = ColumnarBatch(schema, list(batch_cols), int(offsets[-1]),
                              checks)
    from spark_rapids_tpu.columnar.batch import programs_of
    with programs_of("exchange"):       # jit_exchange_slice
        for p in range(len(counts)):
            n = int(counts[p])
            if n == 0:
                out.append(None)
                continue
            out.append(reordered.slice(int(offsets[p]), n))
    return out


@dataclasses.dataclass
class HashPartitioning(TpuPartitioning):
    """murmur3(keys) pmod n — bit-identical to Spark's HashPartitioning so
    TPU and CPU stages can co-shuffle."""
    exprs: Sequence[Expression]
    num_partitions: int

    def bind(self, schema):
        from spark_rapids_tpu.exprs.base import fingerprint
        bound = [e.bind(schema) for e in self.exprs]
        b = HashPartitioning(bound, self.num_partitions)
        b._cache = KernelCache(("HashPartitioning", fingerprint(bound),
                                self.num_partitions))
        return b

    def split_device(self, batch):
        """Phase 1 of the two-phase split: run the device kernel and
        return (cols, device counts, src batch) WITHOUT syncing.  The
        exchange runs this for every input batch back-to-back, then
        overlaps all the count readbacks — one effective round trip for
        the whole map side instead of one per batch."""
        cache = getattr(self, "_cache", None)
        if cache is None:
            cache = self._cache = KernelCache()
        bound = self.exprs
        n = self.num_partitions

        def pid_fn(ctx, salt, extra):
            keys = [e.eval(ctx) for e in bound]
            return partition_ids(keys, n)

        kern = _split_kernel_for(cache, batch, pid_fn, n, "hash")
        cols, counts = kern(batch.columns, batch.num_rows_i32,
                            jnp.int32(0), (), batch.sparse)
        return cols, counts, batch

    @staticmethod
    def finish_split(cols, counts, batch):
        """Phase 2: cut slices with the (prefetched) counts."""
        if batch.capacity > LAZY_SLICE_MAX_CAP:
            from spark_rapids_tpu.utils import checks as CK
            CK.note_host_sync("partition.cut",
                              nbytes=int(counts.size) * 4)
            counts = np.asarray(counts)
        return _slice_partitions(cols, counts, batch.schema,
                                 batch.capacity, batch.checks)

    def partition_batch(self, batch):
        cols, counts, src = self.split_device(batch)
        return self.finish_split(cols, counts, src)


@dataclasses.dataclass
class RoundRobinPartitioning(TpuPartitioning):
    num_partitions: int

    def bind(self, schema):
        b = RoundRobinPartitioning(self.num_partitions)
        b._cache = KernelCache(("RoundRobinPartitioning",
                                self.num_partitions))
        return b

    def partition_batch(self, batch):
        cache = getattr(self, "_cache", None)
        if cache is None:
            cache = self._cache = KernelCache()
        n = self.num_partitions

        def pid_fn(ctx, salt, extra):
            from jax import lax
            return lax.rem(jnp.arange(ctx.capacity, dtype=jnp.int32) + salt,
                           jnp.int32(n))

        kern = _split_kernel_for(cache, batch, pid_fn, n, "rr")
        salt = np.random.randint(0, n)  # start-partition randomization
        cols, counts = kern(batch.columns, batch.num_rows_i32,
                            jnp.int32(salt), (), batch.sparse)
        return _slice_partitions(cols, counts, batch.schema,
                                 batch.capacity, batch.checks)


@dataclasses.dataclass
class SinglePartitioning(TpuPartitioning):
    num_partitions: int = 1

    def partition_batch(self, batch):
        return [batch]


@dataclasses.dataclass
class RangePartitioning(TpuPartitioning):
    """Driver-side reservoir-sampled bounds + per-row binary search
    (reference GpuRangePartitioner/GpuRangePartitioning + SamplingUtils).

    `bounds` are computed once from sampled child data via
    `compute_bounds`; rows route to the first bound >= key.
    """
    order: Sequence  # list[SortOrder]
    num_partitions: int
    bounds: Optional[ColumnarBatch] = None  # (num_partitions-1) rows

    def bind(self, schema):
        from spark_rapids_tpu.exec.sort import SortOrder
        from spark_rapids_tpu.exprs.base import fingerprint
        bound = [SortOrder(o.expr.bind(schema), o.ascending,
                           o.nulls_first) for o in self.order]
        b = RangePartitioning(bound, self.num_partitions, self.bounds)
        # bounds ride in as traced kernel args, so the executable is
        # shareable across bounds values / plan instances
        b._cache = KernelCache(("RangePartitioning", fingerprint(bound),
                                self.num_partitions))
        return b

    @staticmethod
    def compute_bounds(sample: ColumnarBatch, order, num_partitions: int
                       ) -> ColumnarBatch:
        """Sort the sample and take evenly spaced split points."""
        from spark_rapids_tpu.exec.basic import LocalBatchSource
        from spark_rapids_tpu.exec.sort import SortExec
        s = SortExec(order, LocalBatchSource([[sample]]))
        srt = s.collect()
        n = srt.num_rows
        k = num_partitions - 1
        if n == 0 or k <= 0:
            return srt.slice(0, 0)
        idx = [min(n - 1, max(0, int(round((i + 1) * n / num_partitions))))
               for i in range(k)]
        parts = [srt.slice(i, 1) for i in idx]
        from spark_rapids_tpu.columnar.batch import concat_batches
        return concat_batches(parts)

    def partition_batch(self, batch):
        assert self.bounds is not None, "compute_bounds first"
        cache = getattr(self, "_cache", None)
        if cache is None:
            cache = self._cache = KernelCache()
        n = self.num_partitions
        order = self.order
        # key columns of the bounds, aligned to batch capacity for compare
        bounds = self.bounds
        k = bounds.num_rows

        def pid_fn(ctx, salt, extra):
            # composite comparison row-vs-bound via pairwise key compare:
            # pid = number of bounds strictly less-or-equal (k small)
            bcols = extra
            keys = [o.expr.eval(ctx) for o in order]
            pid = jnp.zeros(ctx.capacity, jnp.int32)
            for bi in range(k):
                le = _row_less_than_bound(keys, bcols, bi, order)
                # row > bound_bi -> belongs at least to partition bi+1
                pid = jnp.where(le, pid, jnp.int32(bi + 1))
            return pid

        bounds_sig = tuple(
            (str(c.dtype), c.capacity,
             c.char_cap if c.dtype.is_string else 0)
            for c in bounds.columns)
        kern = _split_kernel_for(cache, batch, pid_fn, n,
                                 ("range", k, bounds_sig))
        cols, counts = kern(batch.columns, batch.num_rows_i32,
                            jnp.int32(0), tuple(bounds.columns),
                            batch.sparse)
        return _slice_partitions(cols, counts, batch.schema,
                                 batch.capacity, batch.checks)


def _row_less_than_bound(keys, bounds, bi: int, order) -> jnp.ndarray:
    """row <= bound_bi under the sort order (null ordering included).
    `bounds` is a ColumnarBatch or a sequence of its key ColumnVectors."""
    from spark_rapids_tpu.exprs.predicates import _compare
    bcols = bounds.columns if hasattr(bounds, "columns") else bounds
    cap = keys[0].capacity
    lt_all = jnp.zeros(cap, bool)
    eq_all = jnp.ones(cap, bool)
    for key_col, o, bcol in zip(keys, order, bcols):
        bv = _broadcast_row(bcol, bi, cap)
        lt, eq = _compare(key_col, bv)
        if not o.ascending:
            lt = ~(lt | eq)
        # null handling: null vs value ordering by nulls_first
        knull = ~key_col.validity
        bnull = ~bv.validity
        nf = o.resolved_nulls_first
        lt = jnp.where(knull & ~bnull, nf, lt)
        lt = jnp.where(~knull & bnull, not nf, lt)
        eqv = jnp.where(knull | bnull, knull & bnull, eq)
        lt_all = lt_all | (eq_all & lt)
        eq_all = eq_all & eqv
    return lt_all | eq_all


def _broadcast_row(col, row: int, cap: int):
    from spark_rapids_tpu.columnar.vector import ColumnVector
    data = jnp.broadcast_to(col.data[row:row + 1], (cap,) +
                            col.data.shape[1:])
    validity = jnp.broadcast_to(col.validity[row:row + 1], (cap,))
    lengths = None if col.lengths is None else jnp.broadcast_to(
        col.lengths[row:row + 1], (cap,))
    return ColumnVector(col.dtype, data, validity, lengths)
