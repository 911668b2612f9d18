"""ICI collective shuffle: the accelerated exchange (reference
`shuffle-plugin/` UCX transport, §2.8(b), re-designed for TPU).

UCX gives the reference RDMA pull: reducers fetch blocks from map outputs.
A TPU pod's strength is the opposite shape — synchronous SPMD collectives
over ICI.  So the accelerated shuffle here is a **push all-to-all**:

  per device (shard_map over the data axis):
    1. murmur3 partition ids for local rows (same bits as the CPU path)
    2. stable sort rows by target device; count per target
    3. scatter rows into a [n_dev, quota, ...] send buffer
    4. lax.all_to_all over the mesh axis  (XLA lowers to ICI all-to-all)
    5. compact received rows into the local output batch

Static shapes: each (src, dst) pair ships exactly `quota` padded rows.
quota = local capacity (worst case: every local row targets one device),
so no data-dependent shapes ever reach XLA.  Overflowing rows cannot occur
under that worst case.

The returned step function is jit-compiled once per schema/capacity and
reused every round — the compile-cache discipline, now pod-wide.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec.base import named_jit
from spark_rapids_tpu.ops.murmur3 import partition_ids as murmur3_pids


def watched_collective(thunk, label: str = "all-to-all",
                       nbytes: int = 0):
    """Run one collective dispatch (and its blocking host readback)
    under a collective-class watchdog heartbeat: an ICI all-to-all
    blocks EVERY mesh participant when one goes dark, so it gets the
    tighter `spark.rapids.sql.watchdog.collectiveTimeout` deadline and
    its own hang-injection site.  A real wedged collective cannot be
    interrupted host-side (the driver is inside the runtime), but the
    watchdog still emits the diagnostic dump naming this dispatch and
    cancels the query so every cooperative wait unwinds.

    `nbytes` (the payload the collective moves over the mesh) feeds
    the query's data-movement ledger — the collective edge of the
    movement report — timed over the dispatch + fence."""
    import time

    from spark_rapids_tpu.utils import movement as MV
    from spark_rapids_tpu.utils import watchdog as W
    with W.heartbeat(f"collective:{label}", kind="collective") as hb:
        W.check_cancelled()
        W.maybe_hang("collective")
        t0 = time.perf_counter_ns()
        out = thunk()
        if nbytes:
            MV.record(MV.EDGE_COLLECTIVE, nbytes, site=label,
                      dur_ns=time.perf_counter_ns() - t0)
        hb.beat()
        return out


def stacked_payload_bytes(arrs) -> int:
    """Ledger convention shared by BOTH collective lanes — the
    hand-rolled mesh exchange (shuffle/exchange.py) and the SPMD
    whole-stage lane (exec/spmd.py): the payload of a mesh collective
    is the total bytes of the stacked arrays ENTERING it (data +
    validity + lengths), regardless of the wire pattern XLA lowers to.
    Using one formula is what lets the two lanes' `collective` edge
    numbers reconcile in tests and bench rounds."""
    total = 0
    for field in arrs:
        for a in field:
            if a is not None:
                total += a.nbytes
    return total


def _local_split(cols, num_rows, key_idx, n_dev, cap):
    """Sort local rows by destination device; return per-dest counts and
    the [n_dev, cap, ...] send buffers."""
    row_mask = jnp.arange(cap) < num_rows
    keys = [cols[i] for i in key_idx]
    pids = murmur3_pids(keys, n_dev)
    pids = jnp.where(row_mask, pids, n_dev)
    order = jnp.argsort(pids, stable=True)
    counts = jnp.bincount(pids, length=n_dev + 1)[:n_dev]
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    # position of each sorted row within its destination block
    sorted_pid = jnp.take(pids, order)
    within = jnp.arange(cap) - jnp.take(starts, jnp.clip(sorted_pid, 0,
                                                         n_dev - 1))
    ok = sorted_pid < n_dev

    def scatter(data):
        src = jnp.take(data, order, axis=0)
        buf = jnp.zeros((n_dev, cap) + data.shape[1:], data.dtype)
        # padded rows go OUT OF RANGE so mode="drop" discards them —
        # mapping them to (0,0) would clobber a real row
        d = jnp.where(ok, sorted_pid, n_dev)
        return buf.at[d, within].set(src, mode="drop")

    return scatter, counts


def exchange_local(local, num_rows, schema: T.Schema, key_idx,
                   n_dev: int, cap: int, axis: str, out_cap=None):
    """The per-device exchange body; call INSIDE shard_map so larger SPMD
    programs (scan->exchange->aggregate in one jit) can fuse around it.

    local: list of (data, validity, lengths|None) local column arrays.
    `out_cap` sizes the compacted received batch; pass n_dev*cap for the
    overflow-proof worst case (every device sends all its rows here) —
    the default (cap) is only safe when the caller pre-padded capacity.
    Returns (list of exchanged (data, validity, lengths|None), total_rows).
    """
    from spark_rapids_tpu.columnar.vector import ColumnVector
    if out_cap is None:
        out_cap = cap
    cols = []
    for f, (data, validity, lengths) in zip(schema.fields, local):
        cols.append(ColumnVector(f.dtype, data, validity, lengths))
    scatter, counts = _local_split(cols, num_rows, key_idx, n_dev, cap)

    recv_counts = jax.lax.all_to_all(
        counts.reshape(n_dev, 1), axis, 0, 0, tiled=False)
    recv_counts = recv_counts.reshape(n_dev)
    starts = jnp.concatenate([jnp.zeros(1, recv_counts.dtype),
                              jnp.cumsum(recv_counts)[:-1]])
    total = recv_counts.sum()
    k = jnp.arange(out_cap)
    src_block = jnp.searchsorted(jnp.cumsum(recv_counts), k, side="right")
    src_block = jnp.clip(src_block, 0, n_dev - 1)
    src_off = k - jnp.take(starts, src_block)
    valid_out = k < total

    out = []
    for data, validity, lengths in local:
        send = scatter(data)
        recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
        gathered = recv[jnp.where(valid_out, src_block, 0),
                        jnp.where(valid_out, src_off, 0)]
        gathered = jnp.where(
            valid_out.reshape((-1,) + (1,) * (data.ndim - 1)),
            gathered, 0)
        vsend = scatter(validity)
        vrecv = jax.lax.all_to_all(vsend, axis, 0, 0, tiled=False)
        vg = vrecv[jnp.where(valid_out, src_block, 0),
                   jnp.where(valid_out, src_off, 0)] & valid_out
        if lengths is not None:
            lsend = scatter(lengths)
            lrecv = jax.lax.all_to_all(lsend, axis, 0, 0, tiled=False)
            lg = lrecv[jnp.where(valid_out, src_block, 0),
                       jnp.where(valid_out, src_off, 0)]
            lg = jnp.where(valid_out, lg, 0)
        else:
            lg = None
        out.append((gathered, vg, lg))
    return out, total


def build_all_to_all_exchange(mesh: Mesh, axis: str,
                              schema: T.Schema,
                              key_indices: Sequence[int],
                              capacity: int, out_capacity=None):
    """Returns a jitted SPMD function:
        (stacked_cols_pytree, num_rows[n_dev]) ->
        (exchanged_cols, new_num_rows[n_dev])
    where stacked arrays have leading dim n_dev sharded over `axis`.

    `out_capacity` (default: capacity) sizes the received batch; pass
    n_dev*capacity for the overflow-proof worst case without having to
    pre-pad the send side.

    Column pytree layout per field: data [n_dev, cap, ...],
    validity [n_dev, cap], lengths or None.
    """
    n_dev = mesh.shape[axis]
    key_idx = tuple(key_indices)

    def per_device(arrs, num_rows):
        # arrs: list of (data, validity, lengths?) with leading dim 1
        # (shard_map gives the local block); squeeze to local views
        local = [tuple(x[0] if x is not None else None for x in a)
                 for a in arrs]
        num_rows = num_rows[0]
        out_local, total = exchange_local(
            local, num_rows, schema, key_idx, n_dev, capacity, axis,
            out_cap=out_capacity)
        out_arrs = [(d[None], v[None], None if l is None else l[None])
                    for d, v, l in out_local]
        return out_arrs, total.astype(jnp.int32)[None]

    specs_per_field = []
    for f in schema.fields:
        if f.dtype.is_string:
            specs_per_field.append((P(axis), P(axis), P(axis)))
        else:
            specs_per_field.append((P(axis), P(axis), None))

    smapped = shard_map(
        per_device, mesh=mesh,
        in_specs=([tuple(P(axis) if i < 2 or f.dtype.is_string else None
                         for i in range(3))
                   for f in schema.fields], P(axis)),
        out_specs=([tuple(P(axis) if i < 2 or f.dtype.is_string else None
                          for i in range(3))
                    for f in schema.fields], P(axis)))
    return named_jit("mesh-exchange", smapped)


def build_count_exchange(mesh: Mesh, axis: str, schema: T.Schema,
                         key_indices: Sequence[int], capacity: int):
    """Phase-1 of the two-phase exchange (ADVICE r2): a counts-only
    all-to-all so the data phase can size its receive buffers from the
    ACTUAL per-device totals instead of the n_dev*cap worst case.
    Returns a jitted fn: (arrs, num_rows[n_dev]) -> recv_total[n_dev]."""
    n_dev = mesh.shape[axis]
    key_idx = tuple(key_indices)

    def per_device(arrs, num_rows):
        local = [tuple(x[0] if x is not None else None for x in a)
                 for a in arrs]
        from spark_rapids_tpu.columnar.vector import ColumnVector
        cols = [ColumnVector(f.dtype, d, v, l)
                for f, (d, v, l) in zip(schema.fields, local)]
        _, counts = _local_split(cols, num_rows[0], key_idx, n_dev,
                                 capacity)
        recv = jax.lax.all_to_all(counts.reshape(n_dev, 1), axis, 0, 0,
                                  tiled=False).reshape(n_dev)
        return recv.sum().astype(jnp.int32)[None]

    smapped = shard_map(
        per_device, mesh=mesh,
        in_specs=([tuple(P(axis) if i < 2 or f.dtype.is_string else None
                         for i in range(3))
                   for f in schema.fields], P(axis)),
        out_specs=P(axis))
    return named_jit("mesh-count", smapped)


def stack_batches(batches, capacity: int):
    """Host helper: stack per-device ColumnarBatches into the pytree
    layout build_all_to_all_exchange expects."""
    import numpy as np
    from spark_rapids_tpu.columnar.vector import _pad_chars
    schema = batches[0].schema
    arrs = []
    for ci, f in enumerate(schema.fields):
        vecs = [b.columns[ci] for b in batches]
        if f.dtype.is_string:
            cc = max(v.char_cap for v in vecs)
            vecs = [_pad_chars(v, cc) for v in vecs]
        vecs = [v for v in vecs]
        data = jnp.stack([v.data for v in vecs])
        validity = jnp.stack([v.validity for v in vecs])
        lengths = (jnp.stack([v.lengths for v in vecs])
                   if vecs[0].lengths is not None else None)
        arrs.append((data, validity, lengths))
    num_rows = jnp.asarray([b.num_rows for b in batches], jnp.int32)
    return arrs, num_rows


def unstack_batches(arrs, num_rows, schema: T.Schema):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.vector import ColumnVector
    n_dev = int(num_rows.shape[0])
    out = []
    for d in range(n_dev):
        cols = []
        for f, (data, validity, lengths) in zip(schema.fields, arrs):
            cols.append(ColumnVector(
                f.dtype, data[d], validity[d],
                None if lengths is None else lengths[d]))
        out.append(ColumnarBatch(schema, cols, int(num_rows[d])))
    return out


@functools.partial(named_jit, "exchange-stack",
                   static_argnames=("cap", "char_caps"))
def local_stack(columns, num_rows, cap: int, char_caps: tuple):
    """One chip's block of the stacked operand, made on that chip (the
    program follows its committed operands): every column padded to
    `cap` rows and, a string column, to the exchange's common char cap,
    under a leading axis of one; `jax.make_array_from_single_device_
    arrays` then assembles the chips' blocks into the mesh-sharded
    arrays `build_all_to_all_exchange` takes, and no byte leaves its
    chip before the all-to-all."""
    def pad(a, cc=0):
        width = [(0, cap - a.shape[0])]
        if a.ndim == 2:
            width.append((0, cc - a.shape[1]))
        return jnp.pad(a, width)[None]

    arrs = [(pad(c.data, cc), pad(c.validity),
             None if c.lengths is None else pad(c.lengths))
            for c, cc in zip(columns, char_caps)]
    return arrs, jnp.minimum(jnp.asarray(num_rows, jnp.int32), cap)[None]


@functools.partial(named_jit, "exchange-unstack")
def local_unstack(arrs):
    """One chip's shard of the exchanged arrays without its leading axis
    of one: output partition d, on chip d."""
    return jax.tree_util.tree_map(lambda a: a[0], arrs)
