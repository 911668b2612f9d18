"""Join operators (reference shims `GpuHashJoin.scala:50,282`,
`GpuShuffledHashJoinExec.scala`, `GpuBroadcastHashJoinExec.scala`,
`GpuBroadcastNestedLoopJoinExec.scala`, `GpuCartesianProductExec.scala`).

TPU equi-join core — exact, static-shape, collision-free:

  1. concat build+probe rows; lexsort by join keys with a side flag as the
     final tie-break (build rows first within each key group);
  2. segment boundaries over the keys give key-groups; per group record the
     build-row range [group_start, group_start + build_count);
  3. each probe row's match count = its group's build count (0 if any key
     is null — SQL equi-join semantics); a CSR expansion enumerates the
     (probe, build) pairs.

The expansion size is data-dependent: kernel A returns counts and the
total syncs to host (one scalar), which picks the output capacity bucket
for kernel B — the bucketed-compile discipline from SURVEY.md §7(a).

Join types: inner, left/right outer, full outer, left semi, left anti,
cross.  Residual (non-equi) conditions post-filter inner/cross joins, as
the reference restricts.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, concat_batches, programs_of, rows_made_known)
from spark_rapids_tpu.columnar.vector import ColumnVector, bucket_capacity
from spark_rapids_tpu.exec.base import (
    KernelCache, RequireSingleBatch, TpuExec, batch_signature,
    make_eval_context, named_jit)
from spark_rapids_tpu.exprs.base import Expression
from spark_rapids_tpu.ops.sort_encode import (
    encode_key_bits, packed_lexsort, segment_boundaries)
from spark_rapids_tpu.utils import checks as CK
from spark_rapids_tpu.utils import metrics as M


from spark_rapids_tpu.columnar.vector import (gather_narrowest,
                                              pack_validity_bits,
                                              validity_bit_assignment)


class JoinType(enum.Enum):
    INNER = "inner"
    LEFT_OUTER = "left_outer"
    RIGHT_OUTER = "right_outer"
    FULL_OUTER = "full_outer"
    LEFT_SEMI = "left_semi"
    LEFT_ANTI = "left_anti"
    CROSS = "cross"


_PROBE_ONLY = (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI)


#: what an `exec:join-probe` span counts, from zero (`capacity_rows`:
#: the sum of the match and expand kernels' capacities; `expand_syncs`:
#: the blocking `join.expand` readbacks)
_PROBE_COUNTS = dict(probe_batches=0, rows_in=0, rows_out=0,
                     capacity_rows=0, expand_syncs=0)


def _owned_dense(b: ColumnarBatch) -> ColumnarBatch:
    """`b.dense()` with its compaction named for the join on the device
    (`jit_join_dense`)."""
    if b.sparse is None:
        return b
    with programs_of("join"):
        return b.dense()


class HashJoinExec(TpuExec):
    """Shuffled hash join: build side concatenated to one batch, probe side
    streamed (reference GpuShuffledHashJoinExec)."""

    def __init__(self, join_type: JoinType,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 left: TpuExec, right: TpuExec,
                 condition: Optional[Expression] = None):
        super().__init__(left, right)
        self.join_type = join_type
        if condition is not None and join_type not in (
                JoinType.INNER, JoinType.CROSS):
            raise ValueError(
                "residual join conditions only supported for inner joins "
                "(same restriction as the reference GpuHashJoin)")
        self.condition = condition
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        lschema, rschema = left.output_schema(), right.output_schema()
        self._lschema, self._rschema = lschema, rschema
        # probe = left, build = right, except RIGHT_OUTER which probes right
        self._flip = join_type == JoinType.RIGHT_OUTER
        if self._flip:
            self._probe, self._build = right, left
            self._probe_keys = [e.bind(rschema) for e in self.right_keys]
            self._build_keys = [e.bind(lschema) for e in self.left_keys]
        else:
            self._probe, self._build = left, right
            self._probe_keys = [e.bind(lschema) for e in self.left_keys]
            self._build_keys = [e.bind(rschema) for e in self.right_keys]

        if join_type in _PROBE_ONLY:
            self._schema = lschema
        else:
            self._schema = T.Schema(tuple(lschema.fields) +
                                    tuple(rschema.fields))
        from spark_rapids_tpu.exprs.base import fingerprint
        self._join_cache = KernelCache((
            "HashJoinExec", join_type.name, self._flip,
            fingerprint(self._probe_keys), fingerprint(self._build_keys),
            fingerprint(condition), fingerprint(lschema),
            fingerprint(rschema)))
        # dense direct-address fast path: single integral equi-key,
        # no residual condition, join types whose output is derivable
        # from a per-probe-row lookup (FULL_OUTER needs unmatched-build
        # emission -> sort path)
        self._dense_qual = (
            condition is None and
            len(self._probe_keys) == 1 and
            self._probe_keys[0].data_type(
                self._probe.output_schema()).is_integral and
            self._build_keys[0].data_type(
                self._build.output_schema()).is_integral and
            join_type in (JoinType.INNER, JoinType.LEFT_OUTER,
                          JoinType.RIGHT_OUTER, JoinType.LEFT_SEMI,
                          JoinType.LEFT_ANTI))
        self._dense_tables: dict = {}

    def output_schema(self) -> T.Schema:
        return self._schema

    def describe(self):
        return (f"HashJoinExec({self.join_type.value}, "
                f"keys={len(self.left_keys)})")

    # -- kernel A: match counts ------------------------------------------
    def _match_kernel(self, build: ColumnarBatch, probe: ColumnarBatch):
        key = ("join-match", batch_signature(build),
               batch_signature(probe))

        def build_fn():
            bcap, pcap = build.capacity, probe.capacity
            cap = bcap + pcap
            build_keys, probe_keys = self._build_keys, self._probe_keys

            @named_jit("join-match")
            def kernel(bcols, bnum, pcols, pnum):
                bctx = make_eval_context(bcols, bcap, bnum)
                pctx = make_eval_context(pcols, pcap, pnum)
                bk = [e.eval(bctx) for e in build_keys]
                pk = [e.eval(pctx) for e in probe_keys]
                # combined key columns (build rows at [0, bcap))
                comb = []
                for b, p in zip(bk, pk):
                    if b.dtype.is_string:
                        from spark_rapids_tpu.columnar.vector import \
                            _pad_chars
                        cc = max(b.char_cap, p.char_cap)
                        b, p = _pad_chars(b, cc), _pad_chars(p, cc)
                        comb.append(ColumnVector(
                            b.dtype,
                            jnp.concatenate([b.data, p.data]),
                            jnp.concatenate([b.validity, p.validity]),
                            jnp.concatenate([b.lengths, p.lengths])))
                    else:
                        dt = b.dtype if b.dtype == p.dtype else \
                            T.common_type(b.dtype, p.dtype)
                        from spark_rapids_tpu.exprs.base import promote
                        b, p = promote(b, dt), promote(p, dt)
                        # both sides' int32 shadows ride along: the key
                        # then sorts as one 32-bit word, not a 64-bit
                        # one, which is more than half of this kernel's
                        # compile for the chip (and of its sort network)
                        narrow = (jnp.concatenate([b.narrow, p.narrow])
                                  if b.narrow is not None
                                  and p.narrow is not None else None)
                        comb.append(ColumnVector(
                            dt, jnp.concatenate([b.data, p.data]),
                            jnp.concatenate([b.validity, p.validity]),
                            None, narrow))
                side = jnp.concatenate([jnp.zeros(bcap, jnp.uint8),
                                        jnp.ones(pcap, jnp.uint8)])
                row_mask = jnp.concatenate([bctx.row_mask, pctx.row_mask])
                keys_msf = [((~row_mask).astype(jnp.uint8), 1)]
                for c in comb:
                    keys_msf.extend(encode_key_bits(c, True, True))
                keys_msf.append((side, 1))
                perm = packed_lexsort(keys_msf)
                bounds = segment_boundaries(comb, perm, row_mask)
                gid = jnp.cumsum(bounds.astype(jnp.int32)) - 1
                sorted_side = jnp.take(side, perm)
                sorted_mask = jnp.take(row_mask, perm)
                keys_ok = jnp.ones(cap, bool)
                for c in comb:
                    keys_ok = keys_ok & c.validity
                sorted_ok = jnp.take(keys_ok, perm) & sorted_mask
                gid_safe = jnp.where(sorted_mask, gid, cap)
                is_build = (sorted_side == 0) & sorted_ok
                is_probe = (sorted_side == 1) & sorted_ok
                bcount = jax.ops.segment_sum(
                    is_build.astype(jnp.int32),
                    jnp.where(is_build, gid_safe, cap), num_segments=cap)
                pcount = jax.ops.segment_sum(
                    is_probe.astype(jnp.int32),
                    jnp.where(is_probe, gid_safe, cap), num_segments=cap)
                (gstart,) = jnp.nonzero(bounds, size=cap,
                                        fill_value=cap - 1)
                # per probe ORIGINAL row: count + start of its build range
                sorted_pos = jnp.arange(cap)
                probe_orig = jnp.where(sorted_side == 1,
                                       jnp.take(perm, sorted_pos) - bcap, 0)
                counts_p = jnp.zeros(pcap, jnp.int32)
                start_p = jnp.zeros(pcap, jnp.int32)
                cnt_for_row = jnp.where(is_probe,
                                        jnp.take(bcount, gid_safe,
                                                 mode="clip"), 0)
                st_for_row = jnp.where(is_probe,
                                       jnp.take(gstart, gid_safe,
                                                mode="clip"), 0)
                sel = sorted_side == 1
                counts_p = counts_p.at[
                    jnp.where(sel, probe_orig, pcap)].add(
                    cnt_for_row.astype(jnp.int32), mode="drop")
                start_p = start_p.at[
                    jnp.where(sel, probe_orig, pcap)].add(
                    st_for_row.astype(jnp.int32), mode="drop")
                # build matched flags (original build rows)
                bmatch_sorted = is_build & (jnp.take(pcount, gid_safe,
                                                     mode="clip") > 0)
                bmatched = jnp.zeros(bcap, bool)
                borig = jnp.where(sorted_side == 0,
                                  jnp.take(perm, sorted_pos), bcap)
                bmatched = bmatched.at[borig].max(bmatch_sorted,
                                                  mode="drop")
                total_inner = counts_p.sum()
                return counts_p, start_p, perm, bmatched, total_inner

            return kernel

        return self._join_cache.get_or_build(
            key, build_fn, meta=self.kp_meta("join-match"))

    # -- kernel B: pair expansion ----------------------------------------
    def _expand_kernel(self, build: ColumnarBatch, probe: ColumnarBatch,
                       out_cap: int, outer_probe: bool):
        key = ("join-expand", outer_probe, out_cap,
               batch_signature(build), batch_signature(probe))

        def build_fn():
            bcap, pcap = build.capacity, probe.capacity
            cap = bcap + pcap

            @named_jit("join-expand")
            def kernel(bcols, pcols, counts_p, start_p, perm, pnum):
                eff = counts_p
                if outer_probe:
                    probe_valid = jnp.arange(pcap) < pnum
                    eff = jnp.where(probe_valid & (counts_p == 0), 1,
                                    counts_p)
                cum = jnp.cumsum(eff)
                total = cum[-1]
                k = jnp.arange(out_cap)
                i = jnp.searchsorted(cum, k, side="right")
                i = jnp.clip(i, 0, pcap - 1)
                prev = jnp.where(i > 0, jnp.take(cum, i - 1, mode="clip"),
                                 0)
                off = k - prev
                in_range = k < total
                has_match = jnp.take(counts_p, i, mode="clip") > 0
                sorted_bpos = jnp.take(start_p, i, mode="clip") + off
                combined_row = jnp.take(perm, jnp.clip(sorted_bpos, 0,
                                                       cap - 1))
                build_row = jnp.clip(combined_row, 0, bcap - 1)
                probe_sel = jnp.where(in_range, i, 0)
                build_sel = jnp.where(in_range & has_match, build_row, 0)
                pvalid = in_range
                bvalid = in_range & has_match
                pout = [c.gather(probe_sel, pvalid) for c in pcols]
                bout = [c.gather(build_sel, bvalid) for c in bcols]
                return pout, bout, total

            return kernel

        return self._join_cache.get_or_build(
            key, build_fn, meta=self.kp_meta("join-expand"))

    def _semi_kernel(self, probe: ColumnarBatch, anti: bool):
        key = ("join-semi", anti, batch_signature(probe))

        def build_fn():
            pcap = probe.capacity

            @named_jit("join-semi")
            def kernel(pcols, counts_p, pnum):
                probe_valid = jnp.arange(pcap) < pnum
                keep = probe_valid & ((counts_p == 0) if anti
                                      else (counts_p > 0))
                n = keep.sum().astype(jnp.int32)
                (idx,) = jnp.nonzero(keep, size=pcap, fill_value=pcap - 1)
                valid = jnp.arange(pcap) < n
                return [c.gather(idx, valid) for c in pcols], n

            return kernel

        return self._join_cache.get_or_build(
            key, build_fn, meta=self.kp_meta("join-semi"))

    # -- dense direct-address fast path -----------------------------------
    # Reference capability parallel: the role cuDF's hash-join build
    # table plays (`GpuHashJoin.scala:282` doJoinLeftRight).  On TPU a
    # pointer-chasing hash table is hostile (serialized gathers), but a
    # DENSE table — one slot per key in [kmin, kmin+span) — turns the
    # whole probe into two fused gathers.  Applicability is checked at
    # build time (span fits budget, keys unique); the sort-merge kernel
    # remains the general fallback.  PK-FK joins on TPC-style dense
    # surrogate keys all take this lane.

    def _try_dense_table(self, build: ColumnarBatch):
        """Build (or fetch cached) the direct-address table; None when
        the build side does not qualify (span too wide / dup keys)."""
        import numpy as np
        from spark_rapids_tpu import config as C
        conf = C.get_active_conf()
        if not conf[C.DENSE_JOIN_ENABLED]:
            return None
        if build.capacity >= (1 << 24) or build.capacity % 128:
            return None  # f32 row-index exactness + pallas lane alignment
        ck = (id(build), build.capacity)
        cached = self._dense_tables.get(ck)
        if cached is not None:
            return cached[0]
        probe = self._join_cache.get_or_build(
            ("dense-probe", batch_signature(build)),
            lambda: named_jit(
                "join-dense-probe",
                self._build_dense_probe(build.capacity)),
            meta=self.kp_meta("join-dense-probe"))
        kmin, kmax = probe(build.columns, build.num_rows_i32)
        kmin, kmax = int(kmin), int(kmax)
        span = kmax - kmin + 1 if kmax >= kmin else 0
        entry = None
        if span <= int(conf[C.DENSE_JOIN_MAX_SPAN]):
            g = int(bucket_capacity(max(span, 1)))
            tab_kern = self._join_cache.get_or_build(
                ("dense-table2", g, batch_signature(build)),
                lambda: named_jit(
                    "join-dense-table",
                    self._build_dense_table_kernel(build.capacity, g)),
                meta=self.kp_meta("join-dense-table"))
            bidx1_tab, vmask_tab, max_cnt = tab_kern(
                build.columns, build.num_rows_i32, jnp.int64(kmin))
            if int(max_cnt) <= 1:  # unique build keys required
                entry = (kmin, g, bidx1_tab, vmask_tab)
        # single-entry cache (repeated collects rebuild the build batch
        # each execute — keeping every old one would pin device memory);
        # the strong ref to the build batch keeps id() valid
        self._dense_tables = {ck: (entry, build)}
        return entry

    def _build_dense_probe(self, cap: int):
        key_expr = self._build_keys[0]

        def probe(columns, num_rows):
            ctx = make_eval_context(columns, cap, num_rows)
            k = key_expr.eval(ctx)
            ok = k.validity & ctx.row_mask
            if k.narrow is not None:
                i32 = jnp.iinfo(jnp.int32)
                kmin = jnp.min(jnp.where(ok, k.narrow, i32.max))
                kmax = jnp.max(jnp.where(ok, k.narrow, i32.min))
                return kmin.astype(jnp.int64), kmax.astype(jnp.int64)
            kd = k.data.astype(jnp.int64)
            i64 = jnp.iinfo(jnp.int64)
            return (jnp.min(jnp.where(ok, kd, i64.max)),
                    jnp.max(jnp.where(ok, kd, i64.min)))
        return probe

    def _build_dense_table_kernel(self, cap: int, g: int):
        """slots <- key - kmin; table[slot] = build row index; counts
        detect duplicates.  Built with an XLA scatter-add — slow on TPU
        but paid ONCE per join build (and cached), unlike the per-probe
        work, and it scales to multi-million-slot tables that the
        one-hot kernel's VMEM cannot hold."""
        key_expr = self._build_keys[0]

        def kernel(columns, num_rows, kmin):
            ctx = make_eval_context(columns, cap, num_rows)
            k = key_expr.eval(ctx)
            ok = k.validity & ctx.row_mask
            if k.narrow is not None:
                offu = (k.narrow - kmin.astype(jnp.int32)
                        ).astype(jnp.uint32)
                in_t = ok & (offu < jnp.uint32(g))
                off = offu.astype(jnp.int32)
            else:
                off64 = k.data.astype(jnp.int64) - kmin
                in_t = ok & (off64 >= 0) & (off64 < g)
                off = off64
            # sentinel slot g: masked rows scatter 0 there; it must read
            # as count 0 for out-of-table probes, so only in_t rows add
            slots = jnp.where(in_t, off, g).astype(jnp.int32)
            cnt_tab = jnp.zeros(g + 1, jnp.int32).at[slots].add(
                in_t.astype(jnp.int32))
            # unique keys are required downstream, so one i32 table
            # carries both the row index AND the occupancy test:
            # bidx1[slot] = build row + 1, 0 = empty slot
            bidx1_tab = jnp.zeros(g + 1, jnp.int32).at[slots].add(
                jnp.where(in_t, jnp.arange(cap, dtype=jnp.int32) + 1, 0))
            # pack every non-string build column's validity into one
            # i32 bitmask per slot: the probe side then resolves ALL
            # column validities with a single gather instead of one
            # bool gather per column (random-access passes dominate
            # probe cost on this chip, ~70ns/row each)
            _, packed = pack_validity_bits(columns)
            if packed is None:
                packed = jnp.zeros(cap, jnp.int32)
            vmask_tab = jnp.zeros(g + 1, jnp.int32).at[slots].add(
                jnp.where(in_t, packed, 0))
            return bidx1_tab, vmask_tab, cnt_tab[:g].max()
        return kernel

    def _dense_key_remat_ordinal(self) -> Optional[int]:
        """Ordinal of the build column the (single) build key reads
        directly, or None.  For an equi-join, that column's matched-row
        values EQUAL the probe key values, so the probe side can
        rematerialize it from the probe key instead of paying a gather
        stream (storage dtypes must agree for bit-exact remat)."""
        from spark_rapids_tpu.exprs.base import BoundReference
        bk = self._build_keys[0]
        if isinstance(bk, BoundReference):
            return bk.ordinal
        return None

    def _dense_probe_kernel(self, build: ColumnarBatch,
                            probe: ColumnarBatch, g: int,
                            narrow_ok: bool):
        key = ("dense-join2", g, narrow_ok, batch_signature(build),
               batch_signature(probe))
        jt = self.join_type

        def build_fn():
            pcap = probe.capacity
            probe_key = self._probe_keys[0]
            remat_ord = self._dense_key_remat_ordinal()

            @named_jit("join-dense")
            def kernel(pcols, pnum, bcols, bidx1_tab, vmask_tab, kmin,
                       pmask=None):
                ctx = make_eval_context(pcols, pcap, pnum, pmask)
                pk = probe_key.eval(ctx)
                ok = pk.validity & ctx.row_mask
                if pk.narrow is not None and narrow_ok:
                    # narrow_ok: the CALLER verified [kmin, kmin+g)
                    # fits int32, so the unsigned-difference window
                    # test is exact (a kmin outside int32 would wrap
                    # and fabricate matches)
                    offu = (pk.narrow - kmin.astype(jnp.int32)
                            ).astype(jnp.uint32)
                    in_t = ok & (offu < jnp.uint32(g))
                    off = offu.astype(jnp.int32)
                else:
                    off64 = pk.data.astype(jnp.int64) - kmin
                    in_t = ok & (off64 >= 0) & (off64 < g)
                    off = off64.astype(jnp.int32)
                slot = jnp.where(in_t, off, g)
                bsel1 = jnp.take(bidx1_tab, slot, mode="clip")
                matched = in_t & (bsel1 > 0)
                if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
                    keep = (ctx.row_mask & ~matched
                            if jt == JoinType.LEFT_ANTI
                            else matched)
                    return keep
                bsel = jnp.where(matched, bsel1 - 1, 0)
                # random-access passes dominate here (~70ns/row each):
                # one bidx1 lookup + one packed-validity lookup + the
                # narrowest possible per-column payload gather, with
                # the build KEY column rematerialized from the probe
                # key (equi-join: matched-row values are equal)
                vm = jnp.take(vmask_tab, slot, mode="clip")
                vbits = validity_bit_assignment(bcols)
                bout = []
                for ci, c in enumerate(bcols):
                    if ci in vbits:
                        valid = matched & (((vm >> vbits[ci]) & 1) != 0)
                    else:
                        valid = matched & jnp.take(c.validity, bsel,
                                                   mode="clip")
                    if (remat_ord == ci
                            and pk.data.dtype == c.data.dtype
                            and not c.dtype.is_string):
                        # matched implies the build key is non-null
                        bout.append(ColumnVector(
                            c.dtype, pk.data, matched, None, pk.narrow))
                    elif c.dtype.is_string:
                        bout.append(c.gather(bsel, matched))
                    else:
                        bout.append(gather_narrowest(c, bsel, valid))
                return bout, matched
            return kernel

        return self._join_cache.get_or_build(
            key, build_fn, meta=self.kp_meta("join-dense"))

    def _execute_dense(self, build, tab, probe_batches, where
                       ) -> Iterator[ColumnarBatch]:
        kmin, g, bidx1_tab, vmask_tab = tab
        jt = self.join_type
        kmin_op = jnp.int64(kmin)
        i32 = np.iinfo(np.int32)
        narrow_ok = i32.min <= kmin and kmin + g <= i32.max

        def probe_one(pb: ColumnarBatch) -> ColumnarBatch:
            with self.metrics.timed(M.TOTAL_TIME):
                kern = self._dense_probe_kernel(build, pb, g, narrow_ok)
                args = (pb.columns, pb.num_rows_i32, build.columns,
                        bidx1_tab, vmask_tab, kmin_op)
                if pb.sparse is not None:
                    args = args + (pb.sparse,)
                if jt in _PROBE_ONLY:
                    keep = kern(*args)
                    return ColumnarBatch(self._schema, pb.columns,
                                         None, pb.checks, sparse=keep)
                elif jt == JoinType.INNER:
                    bout, matched = kern(*args)
                    return self._assemble_sparse(pb.columns, bout,
                                                 matched, pb.checks)
                else:  # LEFT/RIGHT OUTER (probe side preserved)
                    bout, _ = kern(*args)
                    return self._assemble_sparse(pb.columns, bout,
                                                 pb.sparse, pb.checks,
                                                 rows=pb._rows)

        from spark_rapids_tpu.utils import profile as P
        ph = P.phase(P.SPAN_JOIN_PROBE, lane="dense", **_PROBE_COUNTS,
                     **where)
        try:
            for pb in probe_batches:
                if not pb.maybe_nonempty():
                    continue
                if ph is not None:
                    ph.add(probe_batches=1, rows_in=P.known_rows([pb]),
                           capacity_rows=pb.capacity)
                # probe rows are independent given a fixed build
                # table, so the probe side is fully
                # split-and-retry-able
                for out in self.oom_retry_batches(
                        pb, probe_one,
                        label=f"{self.name()}.denseProbe"):
                    if out.maybe_nonempty():
                        if ph is not None:
                            ph.add(rows_out=P.known_rows([out]))
                        self.update_output_metrics(out)
                        yield out
        finally:
            if ph is not None:
                ph.close()

    def _assemble_sparse(self, pcols, bcols, sparse, checks, rows=None):
        if self._flip:
            cols = list(bcols) + list(pcols)
        else:
            cols = list(pcols) + list(bcols)
        return ColumnarBatch(self._schema, cols,
                             rows if sparse is None or rows is not None
                             else None,
                             checks, sparse=sparse)

    # -- execution --------------------------------------------------------
    def children_coalesce_goal(self):
        # build side needs a single batch
        return [None, RequireSingleBatch()] if not self._flip else \
            [RequireSingleBatch(), None]

    def _collect_build_batches(self) -> list[ColumnarBatch]:
        return [_owned_dense(b) for it in self._build.execute_partitions()
                for b in it if b.maybe_nonempty()]

    def _concat_build(self, batches: list[ColumnarBatch]
                      ) -> tuple[ColumnarBatch, int]:
        """The build side made whole, at the capacity of its ROWS, and
        the count reads that took (0 or 1).  Slices that arrive with
        their counts on the device (an exchange's full-capacity cuts)
        would concatenate to the bucketed sum of their capacities, and
        the dense-table attempt and every probe batch's sort would run
        at that: past one batch of padding the counts come to the host
        in one stacked read (`rows_made_known`; the build is a barrier,
        the host blocks on the device right after it anyway) and the
        concat takes its tight branch."""
        from spark_rapids_tpu import config as C
        reads = rows_made_known(
            batches, "join.build", beyond=bucket_capacity(
                int(C.get_active_conf()[C.MAX_BATCH_ROWS])))
        batches = [b for b in batches if b.maybe_nonempty()]
        if not batches:
            from spark_rapids_tpu.columnar.batch import empty_batch
            return empty_batch(self._build.output_schema()), reads
        if len(batches) == 1:
            return batches[0], reads
        # the build-side concat is the join's known OOM hotspot, and a
        # hash join needs the build side WHOLE (single-batch contract),
        # so pressure here spills + retries in place — no split
        from spark_rapids_tpu.memory import retry as R
        nbytes = 2 * sum(b.device_size_bytes() for b in batches)
        return R.with_retry(
            programs_of("join")(lambda: concat_batches(batches)),
            out_bytes=nbytes, metrics=self.metrics,
            label=f"{self.name()}.buildSide"), reads

    def _grace_candidate_batches(self) -> Optional[list[ColumnarBatch]]:
        """Raw build batches when the grace-hash lane may apply, None
        when the build side must be taken whole (broadcast)."""
        if not self._build_keys or not self._probe_keys:
            return None
        return self._collect_build_batches()

    def _assemble(self, pout, bout, n) -> ColumnarBatch:
        """Order output columns as (left, right) regardless of probe side."""
        if self._flip:
            cols = list(bout) + list(pout)
        else:
            cols = list(pout) + list(bout)
        return ColumnarBatch(self._schema, cols, n)

    def co_partitions(self) -> Optional[int]:
        """The partition count when this is a SHUFFLED hash join the
        planner set up partition by partition: both children hash
        exchanges on the join's own keys, key types equal pair by pair
        (murmur3 hashes an INT32 and an INT64 of one value apart) and
        the same number of partitions.  Then rows that can match lie in
        the same partition of both sides, whatever lane an exchange
        takes (both route by murmur3 pmod n).  None for every other
        shape: broadcast, a child that is no exchange (AQE's stage
        readers), range or single partitioning, unequal counts."""
        from spark_rapids_tpu.exprs.base import fingerprint
        from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
        from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
        sides = ((self._build, self._build_keys),
                 (self._probe, self._probe_keys))
        for child, keys in sides:
            if (not keys or not isinstance(child, ShuffleExchangeExec)
                    or child.coalesce_small
                    or not isinstance(child.partitioning, HashPartitioning)
                    or fingerprint(list(child.partitioning.exprs))
                    != fingerprint(keys)):
                return None
        (b, bk), (p, pk) = sides
        n = b.partitioning.num_partitions
        if n != p.partitioning.num_partitions or any(
                x.data_type(b.output_schema())
                != y.data_type(p.output_schema())
                for x, y in zip(bk, pk)):
            return None
        return n

    def execute_columnar(self) -> Iterator[ColumnarBatch]:
        if self.co_partitions() is not None:
            for it in self.execute_partitions():
                yield from it
            return
        # the build side whole against every probe partition.  Under an
        # active mesh the children's partitions may lie a chip each:
        # both sides come to one chip through the counted move (the
        # probe side then waits for all of itself)
        from spark_rapids_tpu.parallel import mesh as PM

        def build_batches():
            batches = self._grace_candidate_batches()
            whole = batches is None
            if whole:
                batches = self._collect_build_batches()
            return PM.to_one_chip(batches, "join-build"), whole

        def probe_batches(build_at):
            return (pb for it in PM.one_chip_partitions(
                self._probe.execute_partitions(), "join-probe",
                device=build_at) for pb in it)
        yield from self._join_sides(build_batches, probe_batches, {})

    def _join_sides(self, build_batches, probe_batches, where: dict
                    ) -> Iterator[ColumnarBatch]:
        """One build side against one probe stream: the whole join, or
        one partition of a co-partitioned one (`where`: its `partition`
        and `device`, for the spans).  `build_batches()` drains the
        build side (and says whether it must be taken whole);
        `probe_batches(chip)` opens the probe stream for a build that
        lies on `chip`."""
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.memory import oocore as OC
        from spark_rapids_tpu.parallel import mesh as PM
        from spark_rapids_tpu.utils import profile as P
        with P.span(P.SPAN_JOIN_BUILD, **where) as sp:
            batches, whole = build_batches()
            build = None
            if whole:
                build, reads = self._concat_build(batches)
            else:
                conf = C.get_active_conf()
                est = 2 * sum(b.device_size_bytes() for b in batches)
                if not OC.should_go_external(est, conf):
                    build, reads = self._concat_build(batches)
            if build is not None:
                at = PM.device_of(build)
                if at is not None and PM.get_active_mesh() is not None:
                    where = dict(where, device=at.id)
                if sp is not None:
                    sp.args = dict(
                        where, rows=P.known_rows([build]),
                        capacity_rows=build.capacity,
                        slices=len(batches), count_reads=reads)
        if build is None:
            P.event(P.EV_OOCORE_DEGRADE, op=self.name(),
                    est_bytes=est, algo="grace-hash")
            probe_src = (pb for pb in probe_batches(None)
                         if pb.maybe_nonempty())
            yield from self._grace_join(iter(batches), probe_src,
                                        0, conf)
            return
        probe_src = probe_batches(at)
        if self._dense_qual:
            tab = self._try_dense_table(build)
            if tab is not None:
                yield from self._execute_dense(build, tab, probe_src,
                                               where)
                return
        yield from self._join_stream(build, probe_src, where)

    def _join_stream(self, build: ColumnarBatch, probe_batches,
                     where: Optional[dict] = None
                     ) -> Iterator[ColumnarBatch]:
        """Sort-path join of one WHOLE build batch against a stream of
        probe batches (the former execute_columnar body, factored out
        so the grace-hash lane can run it once per key partition —
        per-partition FULL_OUTER unmatched-build emission is sound
        because key-hash partitions are key-disjoint)."""
        jt = self.join_type
        outer_probe = jt in (JoinType.LEFT_OUTER, JoinType.RIGHT_OUTER,
                             JoinType.FULL_OUTER)
        bmatched_total = np.zeros(build.capacity, bool)
        from spark_rapids_tpu.utils import profile as P
        ph = P.phase(P.SPAN_JOIN_PROBE, lane="sort", **_PROBE_COUNTS,
                     **(where or {}))

        def probe_one(pb: ColumnarBatch) -> ColumnarBatch:
            pb = _owned_dense(pb)
            if ph is not None:
                ph.add(probe_batches=1, rows_in=P.known_rows([pb]),
                       capacity_rows=build.capacity + pb.capacity,
                       expand_syncs=1)
            with self.metrics.timed(M.TOTAL_TIME):
                mk = self._match_kernel(build, pb)
                counts_p, start_p, perm, bmatched, total_inner = mk(
                    build.columns, jnp.int32(build.num_rows),
                    pb.columns, jnp.int32(pb.num_rows))
                if jt == JoinType.FULL_OUTER:
                    # in-place OR: the flags accumulate across probe
                    # batches AND split pieces (build rows matched by
                    # any piece stay matched)
                    np.logical_or(bmatched_total,
                                  np.asarray(bmatched)[:build.capacity],
                                  out=bmatched_total)
                if jt in _PROBE_ONLY:
                    sk = self._semi_kernel(pb, jt == JoinType.LEFT_ANTI)
                    cols, n = sk(pb.columns, counts_p,
                                 jnp.int32(pb.num_rows))
                    CK.note_host_sync("join.expand", nbytes=4)
                    return ColumnarBatch(self._schema, list(cols), int(n))
                # per-probe-batch host sync: the expand kernel's output
                # capacity must be a HOST int (it keys the compile)
                CK.note_host_sync("join.expand", nbytes=4)
                total = int(total_inner)
                if outer_probe:
                    total = total + pb.num_rows  # upper bound
                out_cap = bucket_capacity(max(total, 1))
                if ph is not None:
                    ph.add(capacity_rows=out_cap)
                ek = self._expand_kernel(build, pb, out_cap, outer_probe)
                pout, bout, tot = ek(build.columns, pb.columns,
                                     counts_p, start_p, perm,
                                     jnp.int32(pb.num_rows))
                out = self._assemble(pout, bout, int(tot))
                if self.condition is not None:
                    out = self._apply_condition(out)
                return out

        try:
            for pb in probe_batches:
                if not pb.maybe_nonempty():
                    continue
                # probe rows are independent given the fixed build side
                # (FULL_OUTER's unmatched-build flags OR across pieces),
                # so probe batches split-and-retry freely while the pair
                # expansion's out_cap shrinks with each piece
                for out in self.oom_retry_batches(
                        pb, probe_one, label=f"{self.name()}.probe"):
                    if out.num_rows > 0:
                        if ph is not None:
                            ph.add(rows_out=out.num_rows)
                        self.update_output_metrics(out)
                        yield out
            if jt == JoinType.FULL_OUTER:
                un = self._unmatched_build(build, bmatched_total)
                if un is not None and un.num_rows > 0:
                    if ph is not None:
                        ph.add(rows_out=un.num_rows)
                    self.update_output_metrics(un)
                    yield un
        finally:
            if ph is not None:
                ph.close()

    # -- grace-hash out-of-core lane ---------------------------------------
    #: base seed for grace partition hashing — deliberately NOT Spark's
    #: seed 42: an upstream HashPartitioning shuffle on the same keys
    #: already bucketed rows by murmur3@42 pmod N, and re-hashing with
    #: the same seed would correlate perfectly and collapse every grace
    #: partition into one
    _GRACE_SALT_BASE = 104729

    def _grace_partition_side(self, batches, bound_keys, nparts: int,
                              depth: int, side: str, conf) -> list[list]:
        """Hash-partition one side of the join by its key columns and
        spill every non-empty slice as an out-of-core run.  The salt is
        a traced kernel argument (one compile serves every recursion
        depth) that varies per depth, so keys that collided at depth d
        scatter at depth d+1."""
        from jax import lax
        from spark_rapids_tpu.memory import oocore as OC
        from spark_rapids_tpu.ops.murmur3 import murmur3_row_hash
        from spark_rapids_tpu.shuffle.partitioning import (
            _slice_partitions, _split_kernel_for)

        def pid_fn(ctx, salt, extra):
            keys = [e.eval(ctx) for e in bound_keys]
            h = murmur3_row_hash(keys, seed=salt)
            m = lax.rem(h, jnp.int32(nparts))
            return jnp.where(m < 0, m + nparts, m)

        salt = jnp.uint32(self._GRACE_SALT_BASE + depth)
        parts: list[list] = [[] for _ in range(nparts)]
        for batch in batches:
            kern = _split_kernel_for(self._join_cache, batch, pid_fn,
                                     nparts, ("grace", side))
            cols, counts = kern(batch.columns, batch.num_rows_i32,
                                salt, (), batch.sparse)
            slices = _slice_partitions(cols, counts, batch.schema,
                                       batch.capacity, batch.checks)
            for p, s in enumerate(slices):
                if s is None or not s.maybe_nonempty():
                    continue
                parts[p].append(OC.spill_run(
                    s.dense(), label=self.name(),
                    metrics=self.metrics, conf=conf))
        return parts

    def _read_runs(self, runs) -> Iterator[ColumnarBatch]:
        for r in runs:
            b = r.read(self.metrics)
            r.free()
            yield b

    def _grace_join(self, build_src, probe_src, depth: int,
                    conf) -> Iterator[ColumnarBatch]:
        """Grace-hash join: partition BOTH sides by key hash into
        spilled runs, join each partition pair that fits the HBM window
        with the normal sort-path core, and recurse (new salt) on pairs
        whose build side still does not fit.  Bounded by
        `oocore.maxRecursionDepth` — irreducible key skew past it is a
        descriptive error, never a hang and never partial data."""
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.memory import oocore as OC
        from spark_rapids_tpu.memory.retry import TpuOutOfCoreError
        from spark_rapids_tpu.utils import profile as P
        from spark_rapids_tpu.utils import watchdog as W
        jt = self.join_type
        nparts = max(2, int(conf[C.OOCORE_GRACE_PARTITIONS]))
        max_depth = max(1, int(conf[C.OOCORE_MAX_RECURSION]))
        window = OC.window_bytes(conf)
        self.metrics.add(M.NUM_GRACE_PARTITIONS, nparts)
        P.event(P.EV_OOCORE_GRACE_PARTITION, op=self.name(),
                num_partitions=nparts, depth=depth)
        build_parts = self._grace_partition_side(
            build_src, self._build_keys, nparts, depth, "build", conf)
        probe_parts = self._grace_partition_side(
            probe_src, self._probe_keys, nparts, depth, "probe", conf)
        for p in range(nparts):
            W.check_cancelled()
            bruns, pruns = build_parts[p], probe_parts[p]
            if not bruns and not pruns:
                continue
            if not pruns and jt != JoinType.FULL_OUTER:
                # build rows with no probe rows only matter to
                # FULL_OUTER's unmatched-build emission
                for r in bruns:
                    r.free()
                continue
            if not bruns and jt in (JoinType.INNER, JoinType.LEFT_SEMI):
                for r in pruns:
                    r.free()
                continue
            best = 2 * sum(r.meta.size_bytes for r in bruns)
            if bruns and best > window:
                if depth + 1 >= max_depth:
                    raise TpuOutOfCoreError(
                        f"{self.name()}: grace-hash build partition {p} "
                        f"is still ~{best} bytes (window {window}) at "
                        f"recursion depth {depth + 1} with "
                        f"spark.rapids.memory.oocore.maxRecursionDepth="
                        f"{max_depth} — the join key is too skewed to "
                        f"partition further (one hot key larger than "
                        f"the window); raise the HBM budget, "
                        f"oocore.windowFraction, or maxRecursionDepth")
                P.event(P.EV_OOCORE_RECURSE, op=self.name(),
                        depth=depth + 1, partition=p)
                yield from self._grace_join(
                    self._read_runs(bruns), self._read_runs(pruns),
                    depth + 1, conf)
                continue
            build_batches = [b.dense() for b in self._read_runs(bruns)]
            build, _ = self._concat_build(build_batches)
            yield from self._join_stream(build, self._read_runs(pruns))

    def _apply_condition(self, batch: ColumnarBatch) -> ColumnarBatch:
        from spark_rapids_tpu.exec.basic import FilterExec, LocalBatchSource
        f = getattr(self, "_cond_filter", None)
        if f is None:
            src = LocalBatchSource([[]], schema=self._schema)
            f = FilterExec(self.condition, src)
            self._cond_filter = f
        out = list(f.process_partition(iter([batch])))
        return out[0]

    def _unmatched_build(self, build: ColumnarBatch,
                         matched: np.ndarray) -> Optional[ColumnarBatch]:
        """FULL OUTER: build rows never matched, with null probe side."""
        if build.num_rows == 0:
            return None
        unmatched = ~matched[: build.num_rows]
        idx = np.nonzero(unmatched)[0]
        if len(idx) == 0:
            return None
        cap = bucket_capacity(len(idx))
        sel = jnp.asarray(np.pad(idx, (0, cap - len(idx))))
        valid = jnp.arange(cap) < len(idx)
        bout = [c.gather(sel, valid) for c in build.columns]
        # null probe columns
        from spark_rapids_tpu.columnar.batch import empty_batch
        pschema = self._probe.output_schema()
        nulls = []
        for f in pschema.fields:
            from spark_rapids_tpu.exprs.base import Literal
            lv = Literal(None, f.dtype)
            ctx = make_eval_context([], cap, jnp.int32(len(idx)))
            nulls.append(lv.eval(ctx))
        return self._assemble(nulls, bout, len(idx))

    def output_partition_count(self) -> int:
        return self.co_partitions() or 1

    def execute_partitions(self):
        """Co-partitioned children: partition p builds from build
        partition p alone and probes with probe partition p, where they
        lie (under a mesh: on chip p), each with its own build concat,
        dense-table attempt, probe stream and grace lane; FULL OUTER's
        unmatched build rows come out partition by partition, sound
        because hash partitions are key-disjoint.  Anything else: one
        partition, the whole build."""
        n = self.co_partitions()
        if n is None:
            return [self.execute_columnar()]
        from spark_rapids_tpu.utils import profile as P
        # as every operator's partitions: the children's iterators are
        # made here, on the caller's thread and under its task (an
        # exchange runs its map side at this point), and partition p's
        # are consumed by whichever thread pulls partition p
        builds = self._build.execute_partitions()
        probes = self._probe.execute_partitions()

        def one(p: int):
            def build_batches():
                return [_owned_dense(b) for b in builds[p]
                        if b.maybe_nonempty()], False
            return self._join_sides(build_batches,
                                    lambda build_at: probes[p],
                                    {"partition": p})
        return [P.wrap_operator(self, p, one(p)) for p in range(n)]


class BroadcastHashJoinExec(HashJoinExec):
    """Same join core; the build side comes from a BroadcastExchangeExec
    so every probe partition reuses one broadcast batch (reference
    GpuBroadcastHashJoinExec)."""

    def _collect_build_batches(self) -> list[ColumnarBatch]:
        from spark_rapids_tpu.shuffle.exchange import BroadcastExchangeExec
        if isinstance(self._build, BroadcastExchangeExec):
            return [self._build.broadcast_batch()]
        return super()._collect_build_batches()

    def _grace_candidate_batches(self) -> Optional[list[ColumnarBatch]]:
        # a broadcast build side is already materialized whole (and
        # shared across consumers) — grace repartitioning it here would
        # not bound anything the broadcast did not already pay
        from spark_rapids_tpu.shuffle.exchange import BroadcastExchangeExec
        if isinstance(self._build, BroadcastExchangeExec):
            return None
        return super()._grace_candidate_batches()


class NestedLoopJoinExec(TpuExec):
    """Brute-force cross/conditioned join (reference
    GpuBroadcastNestedLoopJoinExec / GpuCartesianProductExec — both
    disabled by default there for OOM risk; here the pair expansion is
    bucketed so memory stays bounded per batch pair)."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 condition: Optional[Expression] = None,
                 join_type: JoinType = JoinType.CROSS):
        super().__init__(left, right)
        if join_type not in (JoinType.CROSS, JoinType.INNER):
            raise ValueError("nested loop join supports cross/inner only")
        self.condition = condition
        self._schema = T.Schema(tuple(left.output_schema().fields) +
                                tuple(right.output_schema().fields))
        from spark_rapids_tpu.exprs.base import fingerprint
        self._cache = KernelCache((
            "NestedLoopJoinExec", join_type.name, fingerprint(condition),
            fingerprint(self._schema)))

    def output_schema(self):
        return self._schema

    def _pair_kernel(self, lb: ColumnarBatch, rb: ColumnarBatch):
        key = ("nlj", batch_signature(lb), batch_signature(rb))

        def build_fn():
            lcap, rcap = lb.capacity, rb.capacity
            out_cap = lcap * rcap

            @named_jit("join-nlj")
            def kernel(lcols, lnum, rcols, rnum):
                k = jnp.arange(out_cap)
                li = k // rcap
                ri = k % rcap
                valid = (li < lnum) & (ri < rnum)
                lout = [c.gather(jnp.where(valid, li, 0), valid)
                        for c in lcols]
                rout = [c.gather(jnp.where(valid, ri, 0), valid)
                        for c in rcols]
                # compact valid pairs to the front
                n = valid.sum().astype(jnp.int32)
                (idx,) = jnp.nonzero(valid, size=out_cap,
                                     fill_value=out_cap - 1)
                ok = jnp.arange(out_cap) < n
                lout = [c.gather(idx, ok) for c in lout]
                rout = [c.gather(idx, ok) for c in rout]
                return lout, rout, n

            return kernel

        return self._cache.get_or_build(
            key, build_fn, meta=self.kp_meta("join-nlj"))

    def execute_columnar(self):
        right_batches = [b.dense() for it in
                         self.children[1].execute_partitions()
                         for b in it if b.maybe_nonempty()]
        right_batches = [b for b in right_batches if b.num_rows > 0]
        # pair-expansion budget: the kernel materializes lcap*rcap
        # output rows, so the LEFT side is sharded until one pair
        # block's bytes fit target_size_bytes (the knob the planner
        # threads from spark.rapids.sql.batchSizeBytes — reference
        # GpuBroadcastNestedLoopJoinExec's targetSizeBytes)
        tsb = int(getattr(self, "target_size_bytes", 0)) or (1 << 30)
        row_bytes = max(8 * len(self._schema.fields), 1)
        for it in self.children[0].execute_partitions():
            for lb in it:
                if not lb.maybe_nonempty():
                    continue
                lb = lb.dense()
                if lb.num_rows == 0:
                    continue
                for rb in right_batches:
                    max_left = max(1, tsb // (row_bytes * rb.capacity))
                    pieces = ([lb] if lb.capacity <= max_left else
                              [lb.slice(lo, min(max_left,
                                                lb.num_rows - lo))
                               for lo in range(0, lb.num_rows, max_left)])
                    for piece in pieces:
                        with self.metrics.timed(M.TOTAL_TIME):
                            kern = self._pair_kernel(piece, rb)
                            lout, rout, n = kern(
                                piece.columns, jnp.int32(piece.num_rows),
                                rb.columns, jnp.int32(rb.num_rows))
                            out = ColumnarBatch(
                                self._schema, list(lout) + list(rout),
                                int(n))
                            if self.condition is not None:
                                out = self._apply_condition(out)
                        if out.num_rows:
                            self.update_output_metrics(out)
                            yield out

    def _apply_condition(self, batch):
        from spark_rapids_tpu.exec.basic import FilterExec, LocalBatchSource
        f = getattr(self, "_cond_filter", None)
        if f is None:
            src = LocalBatchSource([[]], schema=self._schema)
            f = FilterExec(self.condition, src)
            self._cond_filter = f
        return list(f.process_partition(iter([batch])))[0]

    def output_partition_count(self) -> int:
        return 1

    def execute_partitions(self):
        return [self.execute_columnar()]


def CartesianProductExec(left: TpuExec, right: TpuExec,
                         condition=None) -> NestedLoopJoinExec:
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.shims import current_shims
    return current_shims(C.get_active_conf()).make_nested_loop_join(
        JoinType.CROSS, left, right, condition)
