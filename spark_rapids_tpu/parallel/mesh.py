"""Device mesh management (the TPU analog of the reference's
`GpuDeviceManager.scala` device discovery/binding, re-thought for SPMD).

The reference binds ONE GPU per executor process and time-shares it across
tasks.  On TPU the idiomatic scaling unit is a `jax.sharding.Mesh` over
all chips: a single SPMD program owns every device, and "executors" become
mesh axis slices.  We expose one canonical data axis for partition
parallelism; multi-host meshes come from jax.distributed initialization
outside (DCN x ICI topology), which `make_mesh` honors by using the global
device list.
"""
from __future__ import annotations

import functools
from contextlib import contextmanager
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def make_mesh(num_devices: Optional[int] = None,
              axis_name: str = DATA_AXIS) -> Mesh:
    devs = jax.devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"make_mesh(num_devices={num_devices}) exceeds the "
                f"{len(devs)} visible device(s) on platform "
                f"'{devs[0].platform if devs else '?'}' — a silently "
                "truncated mesh would shard programs over fewer chips "
                "than the caller planned for.  Request at most "
                f"{len(devs)} devices, or (tests) raise the virtual "
                "device count via "
                "--xla_force_host_platform_device_count.")
        devs = devs[:num_devices]
    return Mesh(np.array(devs), (axis_name,))


# Shardings are memoized per (mesh, axis): hot dispatch paths (every
# SPMD gang dispatch, every mesh-exchange round) ask for the same
# NamedSharding over and over, and constructing one is not free.  The
# bound keeps dead meshes from being pinned forever; jax Meshes hash by
# device set + axis names, so a rebuilt-but-identical mesh still hits.
@functools.lru_cache(maxsize=128)
def data_sharding(mesh: Mesh, axis_name: str = DATA_AXIS) -> NamedSharding:
    """Leading-axis sharding: element i of the stacked batch lives on
    device i of the data axis."""
    return NamedSharding(mesh, P(axis_name))


@functools.lru_cache(maxsize=128)
def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# --- active-mesh registry -------------------------------------------------
# The session-level switch that turns on the accelerated (ICI collective)
# shuffle lane: when a mesh is active, ShuffleExchangeExec routes hash
# exchanges through the mesh all-to-all instead of the local/manager lane —
# the analog of the reference enabling its UCX transport inside the shuffle
# manager (RapidsShuffleInternalManager.scala:199).

_ACTIVE: Optional[tuple[Mesh, str]] = None


def set_active_mesh(mesh: Optional[Mesh],
                    axis_name: str = DATA_AXIS) -> None:
    global _ACTIVE
    _ACTIVE = None if mesh is None else (mesh, axis_name)


def get_active_mesh() -> Optional[tuple[Mesh, str]]:
    return _ACTIVE


@contextmanager
def active_mesh(mesh: Mesh, axis_name: str = DATA_AXIS):
    global _ACTIVE
    prev = _ACTIVE
    set_active_mesh(mesh, axis_name)
    try:
        yield mesh
    finally:
        _ACTIVE = prev


# --- one partition a chip -------------------------------------------------
# Under an active mesh of n chips a plan with n partitions runs partition
# p on chip p: its upload goes there, a jitted program follows its
# committed inputs, and the mesh exchange hands partition d over as chip
# d's own shard.  Nothing below reads a conf: the placement is decided
# from the active mesh and from the device an array is committed to.

def partition_devices(num_partitions: int) -> Optional[list]:
    """Chip p of the active mesh's data axis for each of `num_partitions`
    partitions, or None: no active mesh, one chip, or a partition count
    that is not the mesh size (such a plan stays on the default chip)."""
    if _ACTIVE is None:
        return None
    mesh, axis = _ACTIVE
    if mesh.shape[axis] != num_partitions or num_partitions < 2:
        return None
    return list(mesh.devices.flat)


def device_of(batch, committed_only: bool = True):
    """The one device `batch` is committed to, or None: no column, an
    array spread over several, or one that is committed nowhere (made
    from host values on the default chip: a jitted program takes it to
    wherever its committed operands lie; `committed_only=False` says
    where it lies all the same)."""
    for c in batch.columns:
        devs = c.data.devices()
        if len(devs) == 1 and (not committed_only
                               or getattr(c.data, "committed", True)):
            return next(iter(devs))
        return None
    return None


def _batch_arrays(batch) -> list:
    arrs = [batch.columns, batch.sparse]
    if not batch.num_rows_known:
        arrs.append(batch._rows)
    return arrs


def to_one_chip(batches: list, site: str, device=None,
                strict: bool = False) -> list:
    """THE way a batch changes chips outside the all-to-all: where a plan
    asks for one partition (a top-n merge, `collect()`'s concat, a join
    that takes its build side whole, a range or single exchange), its
    batches come to `device` (default: the chip of the first of them).
    A call that finds every batch there already, or no active mesh,
    returns them as they are and counts nothing; one that moves anything
    is ONE move (`utils/checks.cross_chip_moves`) with its bytes, under
    an `exec:to-one-chip` span.  Deferred checks stay where they are:
    `checks.verify` reads its flags device by device.

    `strict` (the mesh exchange's map side, which assembles its operand
    from single-device arrays): every batch comes back COMMITTED to
    `device`, those committed nowhere too; only bytes that lay on
    another chip are a move."""
    if _ACTIVE is None or not batches:
        return batches
    at = [device_of(b, committed_only=not strict) for b in batches]
    if device is None:
        device = next((d for d in at if d is not None), None)
    if device is None:
        return batches
    away = [i for i, d in enumerate(at) if d is not None and d != device]
    todo = list(range(len(batches))) if strict else away
    if not todo:
        return batches
    import contextlib
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.utils import profile as P
    nbytes = sum(batches[i].device_size_bytes() for i in away)
    # nothing away (strict): committing where it lies copies nothing
    with P.span(P.SPAN_TO_ONE_CHIP, site=site, bytes=nbytes,
                batches=len(away), to=device.id,
                **{"from": sorted({at[i].id for i in away})}) \
            if away else contextlib.nullcontext():
        moved = jax.device_put([_batch_arrays(batches[i]) for i in todo],
                               device)
    if away:
        from spark_rapids_tpu.utils import checks as CK
        CK.note_cross_chip_move(site, nbytes)
    out = list(batches)
    for i, arrs in zip(todo, moved):
        b = batches[i]
        out[i] = ColumnarBatch(b.schema, arrs[0],
                               arrs[2] if len(arrs) > 2 else b._rows,
                               b.checks, sparse=arrs[1])
    return out


def one_chip_partitions(partitions: list, site: str, device=None) -> list:
    """`partitions` with every batch on one chip, for a consumer that
    splits, concatenates or probes across them on ONE device: as they
    are unless an active mesh may have them a chip each, then drained
    and brought together in ONE counted move (`to_one_chip`)."""
    if partition_devices(len(partitions)) is None:
        return partitions
    drained = [list(it) for it in partitions]
    flat = iter(to_one_chip([b for bs in drained for b in bs], site,
                            device=device))
    return [iter([next(flat) for _ in bs]) for bs in drained]
