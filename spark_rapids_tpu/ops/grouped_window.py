"""Windowed grouped-sum over key-SORTED rows — the high-cardinality
grouper (any group count, no dictionary range budget).

Reference parallel: the hash-groupby role cuDF plays for
`GpuHashAggregateExec` (`sql-plugin/.../aggregate.scala:312`) at high
cardinality.  TPU redesign: scatter-free.  With rows sorted by group
key, the group index `gid` is non-decreasing, so a block of R
consecutive rows spans at most R distinct groups and its one-hot
accumulation fits a 2R-wide window of 128-aligned group slabs:

  1. per block b (Pallas, grid over blocks): local table
     [M, 2W] = measures[M, R] @ onehot(gid - slab_base_b)[R, 2W]
     — the one-hot never materializes in HBM and the MXU does the
     accumulation (the plain one-hot matmul is O(rows x groups) and
     infeasible past ~32K groups; this is O(rows x 2R) regardless
     of G).
  2. merge (XLA): slab one-hot [S, B] @ locals[B, M*2W] — B is tiny
     (rows/R), then fold the 2W overlap into [G_pad, M].

No jnp.nonzero / masked_positions / per-measure segmented scans —
the per-group sums land already compact.  Accumulation is f32 (MXU);
callers gate exactness the dict lane's way (|v| certificate for
integers, variableFloatAgg for floats) and extract group keys as
11-bit f32 limb measures (exact by construction: one first-row hit
per group).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_rapids_tpu.ops.pallas_kernels import (_LANES, _note_mosaic,
                                                 _on_tpu, _x64_off)

#: rows per block == slab width.  256 keeps the one-hot [R, 2R] at
#: 256x512 (two MXU tiles) and the locals array at cap/R x M x 2R f32.
WINDOW_ROWS = 256


def _window_block_kernel(g0_ref, gid_ref, *val_and_out, n_measures: int,
                         block_rows: int):
    """One [M, 2W] local table per block: measures @ one-hot(gid-g0)."""
    out_ref = val_and_out[n_measures]
    i = pl.program_id(0)
    w2 = 2 * block_rows
    gid = gid_ref[:]                       # [1, R] lane-major
    rel = gid - g0_ref[i]
    onehot = (jax.lax.broadcast_in_dim(rel, (w2, block_rows), (0, 1)) ==
              jax.lax.broadcasted_iota(jnp.int32, (w2, block_rows), 0)
              ).astype(jnp.float32)        # [2W, R]
    rows = [v[:] for v in val_and_out[:n_measures]]
    stacked = jnp.concatenate(rows, axis=0)  # [M, R]
    # HIGHEST precision: the default TPU matmul rounds f32 inputs to
    # bf16, which silently corrupts measure values (and the exactness
    # certificate's premise); the one-hot matmul is tiny, the 6-pass
    # f32 cost is noise.
    local = jax.lax.dot_general(
        stacked, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)  # [M, 2W]
    mp = out_ref.shape[1]
    out_ref[0] = jnp.pad(local, ((0, mp - n_measures), (0, 0)))


@functools.partial(jax.jit, static_argnames=("out_cap", "capacity",
                                             "interpret",
                                             "interpret_kernel"))
def window_group_sums(gid, vals, *, out_cap: int, capacity: int,
                      interpret: bool = False,
                      interpret_kernel: bool = False):
    """Per-group f32 sums of `vals` (tuple of [capacity] arrays, already
    zeroed on invalid rows) over non-decreasing group ids `gid` (int32,
    rows past the last group may repeat its id).  Returns
    [out_cap, n_measures] f32; groups at or past out_cap are dropped —
    callers pair this with a `num_groups > out_cap` deferred check.

    `interpret=True` (non-TPU backends) computes the same f32 result
    with plain segment sums — running the Mosaic block loop under the
    Pallas interpreter is minutes-per-call at engine widths."""
    n_measures = len(vals)
    if n_measures == 0:
        return jnp.zeros((out_cap, 0), jnp.float32)
    if interpret and not interpret_kernel:
        clamped = jnp.minimum(gid, out_cap)
        return jnp.stack(
            [jax.ops.segment_sum(v.astype(jnp.float32), clamped,
                                 num_segments=out_cap + 1)[:out_cap]
             for v in vals], axis=1)

    r = math.gcd(capacity, WINDOW_ROWS)
    w2 = 2 * r
    n_blocks = capacity // r
    m_pad = max(8, ((n_measures + 7) // 8) * 8)
    s_pad = -(-out_cap // r)                  # slabs of width R

    gid = gid.astype(jnp.int32)
    # slab base per block: 128-aligned... R-aligned floor of the block's
    # FIRST gid; the block's rows then live in [base, base + 2R) because
    # gid grows by at most 1 per row
    gid_first = gid[::r]
    g0 = (gid_first // r) * r
    ins = [gid.reshape(1, -1)] + [v.astype(jnp.float32).reshape(1, -1)
                                  for v in vals]
    block_in = pl.BlockSpec((1, r), lambda i: (0, i))
    if not interpret_kernel:
        _note_mosaic("window_group_sums")
    with _x64_off():
        locals_ = pl.pallas_call(
            functools.partial(_window_block_kernel,
                              n_measures=n_measures, block_rows=r),
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] +
                     [block_in] * (1 + n_measures),
            out_specs=pl.BlockSpec((1, m_pad, w2), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((n_blocks, m_pad, w2),
                                           jnp.float32),
            compiler_params=None if interpret_kernel
            else pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=96 * 1024 * 1024),
            interpret=interpret_kernel,
        )(g0.astype(jnp.int32), *ins)

    # merge across blocks: slab one-hot [S, B] @ locals [B, M*2W].  B is
    # capacity/R (tiny), so this matmul is ~free on the MXU and replaces
    # a serialized scatter-add.
    slab = g0 // r                              # [B]
    onehot = (slab[None, :] == jnp.arange(s_pad, dtype=jnp.int32)[:, None]
              ).astype(jnp.float32)
    merged = jnp.einsum("sb,bmw->smw", onehot,
                        locals_.astype(jnp.float32),
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
    # fold the 2W overlap: slab s's second half lands on slab s+1
    first, second = merged[:, :, :r], merged[:, :, r:]
    carry = jnp.concatenate(
        [jnp.zeros((1,) + second.shape[1:], second.dtype), second[:-1]],
        axis=0)
    table = first + carry                       # [S, M_pad, R]
    out = table.transpose(0, 2, 1).reshape(s_pad * r, m_pad)
    return out[:out_cap, :n_measures]


def use_window_grouper() -> bool:
    return _on_tpu()
