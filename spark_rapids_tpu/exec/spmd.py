"""SPMD whole-stage execution: pjit the fused stage over the device
mesh, not the partition.

PR 7's `FusedStageExec` made a stage ONE XLA program — but Python
still dispatched it once per partition batch, and on a pod that is the
multichip scaling wall: O(partitions) host round-trips per stage while
the mesh sits idle between them.
Theseus (PAPERS.md) argues the runtime must own data movement
end-to-end; the pjit/GDA pattern (SNIPPETS.md [1][2], PartitionSpec
layouts [3]) is the JAX-native form of that for stage compute:

  1. drain the stage's child partitions and STACK every batch along a
     leading slot axis (padded to a common capacity/char-cap, with a
     per-slot row mask so ragged partitions stay bit-exact);
  2. lay the stack out with `NamedSharding(mesh, P("data"))`
     (parallel/mesh.py) — slot i lives on device i % n_dev;
  3. run the whole composed project->filter chain as ONE
     jit-with-shardings program (`jax.vmap` over the slot axis, XLA
     partitions it over the mesh and inserts the cross-shard
     collectives itself: the ANSI-flag any(), the output row-count
     sum, and the output gather back to the engine's default device —
     downstream execution is host-orchestrated single-device work
     today; shard-resident consumption is the pod-scale follow-up);
  4. slice the gathered outputs back into per-partition
     ColumnarBatches in the original order (plain single-device ops).

One Python dispatch per stage, regardless of partition count.

Interop contracts preserved from the per-partition lane:

* bit-exactness: each slot evaluates the same composed expressions on
  the same rows under the same mask the per-partition kernel would
  use — padding rows are masked out, never computed on trust;
* deferred selection: filter stages emit per-slot sparse masks exactly
  like `FilterExec`; pure-project stages pass the input's row
  count/mask through;
* per-member metrics (`FusedStageExec._charge_members` per slot, rows
  as lazy device scalars), OOM reserve/spill/retry at gang granularity
  (`memory/retry.with_retry` over the stacked footprint), watchdog
  collective-class heartbeats (`watched_collective` wraps the gang
  dispatch — a whole-mesh program blocks every participant, so it gets
  the tighter collective deadline and the collective hang-injection
  site), and the movement ledger's `collective` edge (site
  ``spmd-stage``: the payload of the program's implicit cross-shard
  reductions, same bytes-entering-the-collective convention as the
  hand-rolled mesh exchange);
* admission: gang dispatches serialize on the process-wide
  `scheduler.whole_mesh_dispatch` gate (two concurrent whole-mesh
  programs would oversubscribe every chip at once) and take one
  `TpuSemaphore` task hold for the whole mesh.

Deopt (never an error): no active mesh, `spark.rapids.sql.spmd.enabled`
off, uneven batch layouts the stacker cannot unify (mixed narrow-shadow
presence), a gang trace failure, or a prior deopt on this exec — each
falls back to the per-partition fused lane over the already-drained
batches (`numSpmdDeopts`, `spmd_deopt` event).  Compiled gang programs
land in the shared KernelCache under `mesh_cache_scope` keys (mesh
shape + device ids + shardings), so SPMD and per-partition entries can
never collide.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import ColumnVector, _pad_chars
from spark_rapids_tpu.exec.base import (
    make_eval_context, mesh_cache_scope, named_jit)
from spark_rapids_tpu.utils import metrics as M

log = logging.getLogger("spark_rapids_tpu.exec.spmd")

#: site label on the movement ledger's collective edge
SITE_SPMD_STAGE = "spmd-stage"


class SpmdUnsupported(Exception):
    """This gang cannot run SPMD (deopt to the per-partition lane)."""


# ---------------------------------------------------------------------------
# lane counters (bench/CI summary + tests): process-wide so the bench
# can prove "one Python dispatch per stage" without instrumenting jit
_STATS_LOCK = threading.Lock()
_STATS = {"gang_dispatches": 0, "gang_batches": 0, "gang_slots": 0,
          "deopts": 0}


def spmd_stats() -> dict:
    with _STATS_LOCK:
        return dict(_STATS)


def reset_spmd_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def _bump(**kv) -> None:
    with _STATS_LOCK:
        for k, v in kv.items():
            _STATS[k] += v


# ---------------------------------------------------------------------------
def maybe_execute_spmd(exec_) -> Optional[list]:
    """The SPMD lane for one `FusedStageExec`: partition iterators when
    the lane engages, None when the per-partition lane should run
    (conf off, no active mesh, or this exec already deopted).  Conf and
    mesh resolve at EXECUTION time — never captured at plan build."""
    from spark_rapids_tpu.parallel import mesh as PM
    conf = C.get_active_conf()
    if not conf[C.SPMD_ENABLED]:
        return None
    active = PM.get_active_mesh()
    if active is None:
        return None
    if exec_._fusion_deopt or exec_._spmd_deopt:
        return None
    mesh, axis = active

    from spark_rapids_tpu.utils import profile as P
    parts = exec_.child.execute_partitions()
    n_parts = len(parts)
    # the gang barrier: SPMD needs every partition's batches together
    # (that is what one whole-mesh program per stage MEANS)
    entries = [(pi, b) for pi, it in enumerate(parts) for b in it]
    if not entries:
        return [iter(()) for _ in range(n_parts)]

    from spark_rapids_tpu.utils.watchdog import TpuQueryTimeout
    outs = None
    try:
        with exec_.metrics.timed(M.TOTAL_TIME):
            outs = _run_gang(exec_, mesh, axis,
                             [b for _, b in entries])
    except (MemoryError, TpuQueryTimeout):
        raise  # the OOM lattice / watchdog own these
    except Exception as e:  # noqa: BLE001 — unsupported gang shapes
        _note_deopt(exec_, e)  # and trace failures deopt THIS stage

    groups: list[list] = [[] for _ in range(n_parts)]
    if outs is None:
        # per-partition fallback over the already-drained batches: the
        # fused per-batch lane (which may itself deopt further, to the
        # per-operator members)
        for pi, b in entries:
            groups[pi].append(b)
        return [P.wrap_operator(exec_, pi,
                                exec_.process_partition(iter(g)))
                for pi, g in enumerate(groups)]
    for (pi, _), ob in zip(entries, outs):
        groups[pi].append(ob)
    return [P.wrap_operator(exec_, pi, iter(g))
            for pi, g in enumerate(groups)]


def _note_deopt(exec_, err: BaseException) -> None:
    from spark_rapids_tpu.utils import profile as P
    exec_._spmd_deopt = True
    exec_.metrics.add(M.NUM_SPMD_DEOPTS, 1)
    _bump(deopts=1)
    P.event(P.EV_SPMD_DEOPT, members=exec_.stage.member_names(),
            error=f"{type(err).__name__}: {err}"[:300])
    log.warning(
        "SPMD gang for stage [%s] deopted to the per-partition lane: "
        "%s", exec_.stage.describe_ops(), err)


# ---------------------------------------------------------------------------
# stacking
def _gang_layout(schema: T.Schema, batches: list) -> tuple:
    """Unified layout for one gang: (capacity, per-column char_cap,
    per-column narrow-presence).  Raises SpmdUnsupported on layouts the
    stacker cannot unify bit-exactly (mixed narrow shadows: dropping a
    lossy f32 shadow from some slots but not others would route slots
    through DIFFERENT downstream fast paths than the per-partition
    lane)."""
    cap = max(b.capacity for b in batches)
    char_caps: list = []
    narrows: list = []
    for ci, f in enumerate(schema.fields):
        vecs = [b.columns[ci] for b in batches]
        char_caps.append(max(v.char_cap for v in vecs)
                         if f.dtype.is_string else 0)
        with_n = sum(1 for v in vecs if v.narrow is not None)
        if with_n not in (0, len(vecs)):
            raise SpmdUnsupported(
                f"column '{f.name}' carries a narrow shadow on "
                f"{with_n}/{len(vecs)} gang batches — uneven layouts "
                "deopt to the per-partition lane")
        narrows.append(with_n > 0)
    return cap, tuple(char_caps), tuple(narrows)


def _stack_gang(schema: T.Schema, batches: list, cap: int,
                char_caps: tuple, n_slots: int) -> tuple:
    """Stack per-batch columns into [n_slots, cap, ...] pytrees plus
    the per-slot row counts and masks.  Slots past len(batches) are
    zero padding with all-False masks — they flow through the program
    fully masked, so they can never contribute a row."""
    pad_slots = n_slots - len(batches)

    def pad_tail(arr):
        if not pad_slots:
            return arr
        return jnp.concatenate(
            [arr, jnp.zeros((pad_slots,) + arr.shape[1:], arr.dtype)])

    cols: list = []
    for ci, f in enumerate(schema.fields):
        vecs = [b.columns[ci] for b in batches]
        if f.dtype.is_string:
            vecs = [_pad_chars(v, char_caps[ci]) for v in vecs]
        vecs = [v.with_capacity(cap) for v in vecs]
        data = pad_tail(jnp.stack([v.data for v in vecs]))
        validity = pad_tail(jnp.stack([v.validity for v in vecs]))
        lengths = (pad_tail(jnp.stack([v.lengths for v in vecs]))
                   if vecs[0].lengths is not None else None)
        narrow = (pad_tail(jnp.stack([v.narrow for v in vecs]))
                  if vecs[0].narrow is not None else None)
        cols.append(ColumnVector(f.dtype, data, validity, lengths,
                                 narrow))
    num_rows = pad_tail(jnp.stack([b.num_rows_i32 for b in batches]))
    masks = pad_tail(jnp.stack([
        jnp.pad(b.sparse, (0, cap - b.capacity))
        if b.sparse is not None
        else jnp.arange(cap) < b.num_rows_i32 for b in batches]))
    return cols, num_rows, masks


def _stacked_nbytes(cols, masks) -> int:
    total = masks.nbytes + 4 * masks.shape[0]
    for c in cols:
        total += c.data.nbytes + c.validity.nbytes
        if c.lengths is not None:
            total += c.lengths.nbytes
        if c.narrow is not None:
            total += c.narrow.nbytes
    return total


def _tree_nbytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
def _gang_kernel(exec_, mesh, axis: str, cap: int, n_slots: int,
                 col_sig: tuple):
    """One jit-with-shardings program for the whole gang, cached in the
    exec's (stage-fingerprint-scoped) KernelCache under a key that
    includes the mesh shape + shardings — SPMD entries never collide
    with per-partition ones, or with another mesh's."""
    from spark_rapids_tpu.parallel import mesh as PM
    from spark_rapids_tpu.plan.fusion import _eval_stage
    data_shard = PM.data_sharding(mesh, axis)
    repl = PM.replicated(mesh)
    key = ("spmd-stage",
           mesh_cache_scope(mesh, axis, (data_shard.spec,)),
           n_slots, cap, col_sig)

    def build():
        stage = exec_.stage
        has_filter = bool(stage.preds)
        labels: list = []

        def per_slot(cols, nrows, mask):
            ctx = make_eval_context(cols, cap, nrows, mask)
            out_cols, keep, counts = _eval_stage(stage, ctx)
            labels.clear()
            labels.extend(l for l, _ in ctx.pending_checks)
            return (out_cols, keep, tuple(counts),
                    tuple(f for _, f in ctx.pending_checks))

        def gang(cols, nrows, mask):
            out_cols, keep, counts, pend = \
                jax.vmap(per_slot)(cols, nrows, mask)
            # the program's only CROSS-SHARD traffic — XLA inserts the
            # collectives for these replicated reductions itself:
            # one any() per deferred-check flag, one sum() for the
            # stage's total output rows (charged lazily to the fused
            # node's metrics, no host sync)
            pend = tuple(jnp.any(f) for f in pend)
            rows = counts[-1] if counts else nrows
            total = rows.sum().astype(jnp.int32)
            return out_cols, keep, counts, pend, total

        kernel = named_jit(
            "spmd-gang", gang,
            in_shardings=(data_shard, data_shard, data_shard),
            out_shardings=(data_shard, data_shard, data_shard, repl,
                           repl))
        kernel._ansi_labels = labels
        return kernel

    # gang kernels carry member attribution like the per-partition
    # fused lane: one catalog entry per (mesh, stage, layout) whose
    # members name the operators the sharded program evaluates
    return exec_.kernels.get_or_build(
        key, build,
        meta=exec_.kp_meta("spmd-gang",
                           members=exec_.stage.member_names())), \
        data_shard


def _run_gang(exec_, mesh, axis: str, batches: list) -> list:
    """Dispatch one gang: stack, shard, run, unstack.  Returns one
    output ColumnarBatch per input batch, in order."""
    from spark_rapids_tpu.exec import scheduler as S
    from spark_rapids_tpu.exec.basic import _register_ansi
    from spark_rapids_tpu.memory import retry as R
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    from spark_rapids_tpu.parallel.collective_exchange import (
        watched_collective)
    from spark_rapids_tpu.utils import movement as MV
    from spark_rapids_tpu.utils import profile as P

    stage = exec_.stage
    schema = stage.in_schema
    n_dev = mesh.shape[axis]
    # the stacker builds the gang on one device and scatters it: source
    # partitions that were uploaded a chip each come together first
    from spark_rapids_tpu.parallel import mesh as PM
    batches = PM.to_one_chip(batches, "spmd-gang")
    B = len(batches)
    n_slots = -(-B // n_dev) * n_dev
    cap, char_caps, narrows = _gang_layout(schema, batches)
    col_sig = tuple(
        (f.dtype.id.value, char_caps[ci], narrows[ci])
        for ci, f in enumerate(schema.fields))

    # trace/compile OUTSIDE the dispatch gate (KernelCache single-
    # flight already serializes same-key builders)
    kernel, data_shard = _gang_kernel(exec_, mesh, axis, cap, n_slots,
                                      col_sig)
    cols, num_rows, masks = _stack_gang(schema, batches, cap,
                                        char_caps, n_slots)
    est_bytes = _stacked_nbytes(cols, masks)

    first = not getattr(kernel, "_spmd_reported", False)
    t0 = time.perf_counter() if first else 0.0
    # one task hold covers the whole mesh: the gang IS the stage's
    # device occupancy, not one hold per partition
    TpuSemaphore.get().acquire_if_necessary()
    has_filter = bool(stage.preds)
    out_schema = exec_.output_schema()
    outs: list = []
    # the gang's outputs converge to the engine's DEFAULT device: the
    # whole downstream engine is host-orchestrated single-device work
    # today, and slicing a still-sharded array per slot enqueues one
    # whole-mesh program per slice (measured ~100x the kernel's own
    # cost on the 8-device CPU mesh, and a rendezvous-deadlock vector
    # outside the gate).  Shard-resident consumption is the pod-scale
    # follow-up (ROADMAP items 1/6).
    from jax.sharding import SingleDeviceSharding
    home = SingleDeviceSharding(jax.devices()[0])

    def dispatch():
        out = kernel(*inputs)
        # the output gather IS the program's main implicit collective:
        # every non-home shard's bytes cross the mesh here, inside the
        # watched/timed region
        return jax.device_put(out, home)

    # the gate covers every whole-mesh enqueue (input scatter, gang
    # program, output gather): concurrent whole-mesh enqueues from two
    # threads can invert per-device queue order and deadlock the
    # collective rendezvous (exec/scheduler.py).  The stacked gang
    # inputs are device-pinned for the dispatch — the residency ledger
    # carries them so a gang's footprint shows in the owning query's
    # high-water composition
    from spark_rapids_tpu.utils import residency as RES
    with RES.tracked(est_bytes, site="spmd-gang",
                     kind=RES.KIND_GANG), \
            S.whole_mesh_dispatch(label=stage.describe_ops()):
        inputs = jax.device_put((cols, num_rows, masks), data_shard)
        t_disp = time.perf_counter_ns()
        out_cols, keep, counts, pend, total = R.with_retry(
            lambda: watched_collective(
                dispatch, label=f"spmd:{stage.describe_ops()}"),
            out_bytes=est_bytes, metrics=exec_.metrics,
            label=f"SpmdStage[{stage.describe_ops()}]")
        disp_ns = time.perf_counter_ns() - t_disp
    # post-gather, slicing is plain single-device work: no whole-mesh
    # enqueues escape the gate
    wave_checks = _register_ansi(pend, kernel._ansi_labels)
    for slot, b in enumerate(batches):
        slot_cols = [
            ColumnVector(
                f.dtype, cv.data[slot], cv.validity[slot],
                None if cv.lengths is None else cv.lengths[slot],
                None if cv.narrow is None else cv.narrow[slot])
            for f, cv in zip(out_schema.fields, out_cols)]
        checks = b.checks + wave_checks
        slot_counts = tuple(c[slot] for c in counts)
        if has_filter:
            out_b = ColumnarBatch(out_schema, slot_cols,
                                  slot_counts[-1], checks,
                                  sparse=keep[slot])
        elif b.sparse is not None:
            out_b = ColumnarBatch(out_schema, slot_cols, b._rows,
                                  checks, sparse=keep[slot])
        else:
            out_b = ColumnarBatch(out_schema, slot_cols, b._rows,
                                  checks)
        exec_._charge_members(b, slot_counts)
        outs.append(out_b)
    # one event per gang dispatch (one per stage execution — cheap);
    # a jit's first call traces + compiles synchronously, so the
    # first-dispatch delta IS the gang's compile cost
    kernel._spmd_reported = True
    P.event(P.EV_STAGE_SPMD, members=stage.member_names(),
            batches=B, slots=n_slots, mesh_devices=int(n_dev),
            **({"compile_ms": round((time.perf_counter() - t0) * 1e3,
                                    2)} if first else {}))
    _bump(gang_dispatches=1, gang_batches=B, gang_slots=n_slots)
    exec_.metrics.add(M.NUM_SPMD_DISPATCHES, 1)
    if MV.ledger() is not None and n_dev > 1:
        # the implicit collectives' payload: the stage outputs
        # entering the output gather, plus the cross-shard flag /
        # row-count reductions — the same bytes-entering-the-
        # collective convention as the hand-rolled lane's
        # stacked_payload_bytes, so the two lanes' collective-edge
        # numbers reconcile
        implicit = _tree_nbytes((out_cols, keep, counts, pend, total))
        MV.record(MV.EDGE_COLLECTIVE, implicit, site=SITE_SPMD_STAGE,
                  dur_ns=disp_ns)
        exec_.metrics.add(M.COLLECTIVE_BYTES, implicit)
    # stage totals ride the replicated device scalar (one add, lazy)
    exec_.metrics.add(M.NUM_OUTPUT_ROWS, total)
    exec_.metrics.add(M.NUM_OUTPUT_BATCHES, B)
    return outs
