"""Pallas TPU kernels for the engine's hot ops.

Reference parallel: the role cuDF's fused groupby-aggregate kernels play
under `GpuHashAggregateExec` (`aggregate.scala:312`): a
scan→filter→project→group-reduce pipeline as one explicit pass over
HBM, the group table living in VMEM the whole time.

Why this formulation: XLA must materialize the [rows, 6] values and
[rows, 8] one-hot einsum operands in HBM, while this kernel keeps them
in VMEM and touches each input byte once; reductions are lane-wise
partials in-kernel (sublane-axis sums only, at full VPU width) with one
deferred f64 cross-lane combine outside.  `spark.rapids.tpu.pallas.
q1Fused.enabled` defaults on for the stacked-Q1 step; single-batch
dispatches stay on the XLA kernel.  Speed relative to the XLA kernel:
not measured on the current machine.

Kernels run in interpret mode off-TPU, so the CPU test suite exercises
the same code path the chip runs (`pl.pallas_call(..., interpret=True)`).
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 1 << 18        # rows per grid step: 7 inputs x 1MB x 2
                            # (double-buffer) = 14MB of VMEM
_LANES = 128


def _x64_off():
    """Context disabling x64 during kernel tracing (Mosaic cannot
    legalize the i64 index-map constants x64 promotion creates)."""
    return jax.enable_x64(False)


def _on_tpu() -> bool:
    """Mosaic kernels compile only for a TPU backend; every other
    backend (the CPU test lane) takes the kernels' `interpret` lane."""
    return jax.default_backend() == "tpu"


#: Mosaic (non-interpret) `pallas_call` sites traced in this process,
#: by kernel name.  A jitted program that traced one and then ran
#: dispatched it, so chip_smoke.py reads this to prove a query's
#: aggregate really took the Pallas lane on the chip.
_MOSAIC_TRACES: collections.Counter = collections.Counter()


def _note_mosaic(name: str) -> None:
    _MOSAIC_TRACES[name] += 1


def mosaic_trace_counts() -> dict:
    return dict(_MOSAIC_TRACES)


def _q1_block_kernel(nrows_ref, flag_ref, status_ref, qty_ref, price_ref,
                     disc_ref, tax_ref, ship_ref, out_ref, *,
                     cutoff: int, block_rows: int, batch_rows: int):
    """One block: filter + project + group x measure LANE-WISE sums.

    Output block (48, 128) — 8 group slots x 6 measures, 8-aligned for
    the sublane tiling; rows for groups 6-7 are zero padding.  Row
    g*6+j holds measure j's per-lane partial for group g.  Only the sublane axis is reduced in-kernel — the VPU
    does that at full lane width; the 128-lane cross reduction (and the
    f64 combine) happens once outside.  Round 1 reduced all the way to
    scalars per block (48 cross-lane reductions) and ran 5x slower than
    XLA; this formulation is the one that beats it.

    `batch_rows` supports stacked multi-batch dispatch: rows belong to
    batch ridx // batch_rows, each with its own num_rows in the SMEM
    vector (block_rows must divide batch_rows so a block never straddles
    batches)."""
    i = pl.program_id(0)
    flag = flag_ref[:]
    status = status_ref[:]
    qty = qty_ref[:]
    price = price_ref[:]
    disc = disc_ref[:]
    tax = tax_ref[:]
    ship = ship_ref[:]
    batch = (i * jnp.int32(block_rows)) // jnp.int32(batch_rows)
    nrows = nrows_ref[batch]
    local_base = (i * jnp.int32(block_rows)) % jnp.int32(batch_rows)

    shape = flag.shape
    ridx = (local_base
            + jax.lax.broadcasted_iota(jnp.int32, shape, 0) * _LANES
            + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    keep = (ridx < nrows) & (ship <= jnp.int32(cutoff))
    disc_price = price * (jnp.float32(1.0) - disc)
    charge = disc_price * (jnp.float32(1.0) + tax)
    gid = jnp.where(keep, flag * jnp.int32(2) + status, jnp.int32(7))
    measures = (qty, price, disc_price, charge, disc, None)

    zeros = jnp.zeros((_LANES,), jnp.float32)
    for g in range(8):
        if g >= 6:
            # padding rows: blocks must be written whole (48 = 8-aligned)
            for j in range(6):
                out_ref[g * 6 + j, :] = zeros
            continue
        in_g = gid == g
        for j, v in enumerate(measures):
            # jnp.where, not multiply: NaN in a filtered row must not
            # poison the sum; counts reuse the mask itself
            vm = (in_g.astype(jnp.float32) if v is None
                  else jnp.where(in_g, v, jnp.float32(0)))
            out_ref[g * 6 + j, :] = jnp.sum(vm, axis=0)


@functools.partial(jax.jit, static_argnames=("capacity", "cutoff",
                                             "batch_rows", "interpret"))
def q1_fused_pallas(flag, status, qty, price, disc, tax, ship,
                    num_rows, *, capacity: int, cutoff: int,
                    batch_rows: int = 0, interpret: bool = False):
    """TPC-H Q1 scan→filter→project→group-reduce as one Pallas pass.

    `batch_rows` > 0 runs the STACKED multi-batch form: the columns hold
    B = capacity // batch_rows batches back to back and `num_rows` is a
    (B,) vector — one dispatch aggregates them all (the device-side
    batch loop that amortizes per-dispatch runtime overhead).

    Returns the (8, 6) float64 group table (per-block f32 lane partials
    are combined in f64, so millions of rows do not lose the
    accumulator's low bits)."""
    if capacity < _LANES:
        # tiny capacity buckets (32, 64) pad up to one full lane row;
        # the num_rows mask keeps the padding out of every sum
        pad = _LANES - capacity
        flag, status, ship = (jnp.pad(x, (0, pad))
                              for x in (flag, status, ship))
        qty, price, disc, tax = (jnp.pad(x, (0, pad))
                                 for x in (qty, price, disc, tax))
        capacity = _LANES
    if batch_rows <= 0:
        batch_rows = capacity
    block_rows = min(BLOCK_ROWS, batch_rows)
    assert capacity % batch_rows == 0 and \
        batch_rows % block_rows == 0 and block_rows % _LANES == 0, \
        (capacity, batch_rows)
    # mosaic block constraint: unless the block covers the whole array,
    # its sublane count must be a multiple of 8 (1024 rows); callers
    # (build_q1_fused_kernel) route smaller stacked batches to the XLA
    # fallback instead
    if capacity != block_rows:
        assert block_rows % (8 * _LANES) == 0, (
            f"stacked batch_rows={batch_rows} needs a multiple of 1024 "
            "rows per block for mosaic tiling")
    sublanes = block_rows // _LANES
    n_blocks = capacity // block_rows

    def shape2d(x, dtype):
        return x.astype(dtype).reshape(n_blocks * sublanes, _LANES)

    ins = (shape2d(flag, jnp.int32), shape2d(status, jnp.int32),
           shape2d(qty, jnp.float32), shape2d(price, jnp.float32),
           shape2d(disc, jnp.float32), shape2d(tax, jnp.float32),
           shape2d(ship, jnp.int32))
    nrows = jnp.asarray(num_rows, jnp.int32).reshape(-1)
    block_in = pl.BlockSpec((sublanes, _LANES), lambda i: (i, 0))
    # the engine enables x64 globally (Spark parity), but mosaic cannot
    # legalize the i64 index-map constants x64 promotion creates — trace
    # the kernel with x64 off (every dtype in it is explicit i32/f32)
    if not interpret:
        _note_mosaic("q1_fused_pallas")
    with _x64_off():
        partials = pl.pallas_call(
            functools.partial(_q1_block_kernel, cutoff=cutoff,
                              block_rows=block_rows,
                              batch_rows=batch_rows),
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] +
                     [block_in] * 7,
            out_specs=pl.BlockSpec((48, _LANES), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n_blocks * 48, _LANES),
                                           jnp.float32),
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                # 7 double-buffered 1MB input blocks + temporaries blow
                # the default 16MB scoped-vmem budget; v5e has 128MB
                vmem_limit_bytes=64 * 1024 * 1024),
            interpret=interpret,
        )(nrows, *ins)
    # f64 cross-block + cross-lane combine (same numerics as XLA kernel)
    return partials.reshape(n_blocks, 8, 6, _LANES).astype(
        jnp.float64).sum(axis=(0, 3))


def build_q1_kernel_pallas(capacity: int, cutoff: int,
                           interpret: bool | None = None):
    """Drop-in alternative to models.tpch.build_q1_kernel with the same
    output contract, backed by the fused Pallas pass."""
    if interpret is None:
        interpret = not _on_tpu()

    def q1_step(flag, status, qty, extprice, disc, tax, shipdate,
                num_rows):
        table = q1_fused_pallas(
            flag, status, qty, extprice, disc, tax, shipdate, num_rows,
            capacity=capacity, cutoff=cutoff, interpret=interpret)
        table = table.T  # (6 measures, 8 groups) like the XLA kernel
        g = jnp.arange(8)
        cnt = table[5].astype(jnp.int32)
        return (g // 2, g % 2, table[0], table[1], table[2], table[3],
                table[4], cnt)

    return q1_step


# ---------------------------------------------------------------------------
# Grouped sum/count for DICTIONARY-ENCODED keys (key ids in [0, n_groups)).
#
# The engine's general hash aggregate sorts rows by key (packed-word
# lexsort) because XLA:TPU scatter serializes — but sorting is the
# expensive part (bitonic, O(n log^2 n)).  When the key domain is a known
# dense dictionary (categoricals, already-dictionary-encoded columns, the
# BASELINE milestone-2 shape), grouping is a single HBM pass: per block,
# build the [rows, groups] one-hot in VMEM and matmul it against the
# measures on the MXU, accumulating the [groups, measures] table across
# sequential grid steps.  No sort, no scatter, input bytes touched once.
#
# Sums carry f32-accumulator tolerance (~1e-3 relative over millions of
# rows) -- the variableFloatAgg semantics Spark already gates float sums
# behind.  Speed against the sort-based aggregate: not measured on the
# current machine.

_GROUP_BLOCK_ROWS = 1 << 13   # one-hot VMEM budget caps rows x groups


def _grouped_sum_kernel(nrows_ref, keys_ref, *val_and_out,
                        n_groups: int, n_measures: int, block_rows: int):
    """Blocks are LANE-MAJOR [1, block_rows]: the one-hot builds by
    broadcasting the key lane-vector across G sublanes (the native
    direction — sublane-flatten reshapes don't lower in mosaic), and one
    [G, R] x [M+1, R]^T matmul per block feeds the MXU."""
    vals = val_and_out[:n_measures]
    out_ref = val_and_out[n_measures]
    cnt_ref = val_and_out[n_measures + 1]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    keys = keys_ref[:]                      # [1, R]
    base = i * jnp.int32(block_rows)
    ridx = base + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    valid = ridx < nrows_ref[0]
    k = jnp.where(valid, keys, jnp.int32(n_groups))
    kb = jax.lax.broadcast_in_dim(k, (n_groups, keys.shape[1]), (0, 1))
    onehot = (kb == jax.lax.broadcasted_iota(
        jnp.int32, (n_groups, keys.shape[1]), 0)).astype(jnp.float32)
    rows = [jnp.where(valid, v[:], jnp.float32(0)) for v in vals]
    rows.append(valid.astype(jnp.float32))
    stacked = jnp.concatenate(rows, axis=0)  # [M+1, R] lane-major
    table = jax.lax.dot_general(
        onehot, stacked, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # [G, M+1]
    gp, mp = out_ref.shape
    table = jnp.pad(table, ((0, gp - n_groups), (0, mp - n_measures - 1)))
    out_ref[:] = out_ref[:] + table
    # counts accumulate in INT32: a per-block count <= block_rows is
    # exact in f32, but cross-block f32 accumulation would silently
    # saturate past 2^24 rows per group
    cnt = table[:, n_measures].astype(jnp.int32)
    cnt_ref[:] = cnt_ref[:] + jnp.pad(
        cnt[:, None], ((0, 0), (0, cnt_ref.shape[1] - 1)))


@functools.partial(jax.jit, static_argnames=("n_groups", "capacity",
                                             "interpret",
                                             "interpret_kernel"))
def grouped_sum_pallas(keys, vals, num_rows, *, n_groups: int,
                       capacity: int, interpret: bool = False,
                       interpret_kernel: bool = False):
    """sums/counts per dictionary key id: keys int32 in [0, n_groups),
    vals a tuple of f32 arrays.  Returns ([n_groups, n_measures] f64
    sums, [n_groups] int32 counts).  Rows with out-of-range keys are
    COUNTED INVALID (masked) — callers guarantee the dictionary.

    `interpret=True` (any non-TPU backend) computes the same masked
    f32-accumulated result with plain segment sums instead of running
    the Mosaic kernel under the Pallas INTERPRETER — interpretation
    executes the block loop in Python and made the virtual-CPU test
    suite minutes slower per workload query once integral sums
    started qualifying for this lane.  `interpret_kernel=True` still
    runs the Mosaic kernel under the interpreter;
    `tests/test_pallas.py` compares it against this fallback so the
    two lanes cannot silently diverge."""
    import math
    assert capacity % _LANES == 0
    if interpret and not interpret_kernel:
        rows_ok = jnp.arange(capacity) < jnp.asarray(num_rows, jnp.int32)
        k = jnp.where(rows_ok, keys, n_groups)
        in_range = (k >= 0) & (k < n_groups)
        seg = jnp.where(in_range, k, n_groups)
        counts = jnp.bincount(seg, length=n_groups + 1)[:n_groups] \
            .astype(jnp.int32)
        sums = jnp.stack(
            [jax.ops.segment_sum(
                jnp.where(in_range, v.astype(jnp.float32), 0), seg,
                num_segments=n_groups + 1)[:n_groups]
             for v in vals], axis=1) if vals else \
            jnp.zeros((n_groups, 0), jnp.float32)
        return sums.astype(jnp.float64), counts
    n_measures = len(vals)
    g_budget_rows = (48 * 1024 * 1024 // (4 * max(n_groups, 1))
                     ) // _LANES * _LANES
    block_rows = max(_LANES, min(_GROUP_BLOCK_ROWS, capacity,
                                 max(g_budget_rows, _LANES)))
    # block must divide capacity WITHOUT abandoning the VMEM budget:
    # gcd keeps a 128-multiple divisor <= the budgeted size
    block_rows = max(_LANES, math.gcd(capacity, block_rows))
    n_blocks = capacity // block_rows
    g_pad = ((n_groups + 7) // 8) * 8
    m_pad = ((n_measures + 1 + _LANES - 1) // _LANES) * _LANES

    def lane_major(x, dtype):
        return x.astype(dtype).reshape(1, -1)

    ins = [lane_major(keys, jnp.int32)] + [lane_major(v, jnp.float32)
                                           for v in vals]
    nrows = jnp.asarray(num_rows, jnp.int32).reshape(1)
    block_in = pl.BlockSpec((1, block_rows), lambda i: (0, i))
    if not (interpret or interpret_kernel):
        _note_mosaic("grouped_sum_pallas")
    with _x64_off():
        table, cnt_tab = pl.pallas_call(
            functools.partial(_grouped_sum_kernel, n_groups=n_groups,
                              n_measures=n_measures,
                              block_rows=block_rows),
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] +
                     [block_in] * (1 + n_measures),
            out_specs=[pl.BlockSpec((g_pad, m_pad), lambda i: (0, 0)),
                       pl.BlockSpec((g_pad, _LANES), lambda i: (0, 0))],
            out_shape=[
                jax.ShapeDtypeStruct((g_pad, m_pad), jnp.float32),
                jax.ShapeDtypeStruct((g_pad, _LANES), jnp.int32)],
            compiler_params=None if (interpret or interpret_kernel)
            else pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=96 * 1024 * 1024),
            interpret=interpret or interpret_kernel,
        )(nrows, *ins)
    sums = table[:n_groups, :n_measures].astype(jnp.float64)
    counts = cnt_tab[:n_groups, 0]
    return sums, counts
