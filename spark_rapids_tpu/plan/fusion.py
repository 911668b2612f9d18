"""Whole-stage XLA fusion: compile operator chains into one program
per stage.

What NOT doing this costs is per-operator dispatch plus an intermediate
ColumnarBatch materialized in HBM between every two operators (the
hand-fused q1 kernel of models/tpch.py is the same query without
either; the gap is not measured on the current machine).  Eiger
(PAPERS.md) makes the general case: relational operator pipelines
should compile into single kernels, with the pipeline breaks as the
only boundaries.

This pass walks the physical plan between pipeline breaks — exchange,
coalesce, AQE stage boundaries, sort, join build are never crossed
because only Project/Filter (and the aggregate update lane) are
fusible — and collapses:

* `project -> filter -> project` chains (any mix, length >= 2) into a
  `FusedStageExec` whose batch function is ONE jitted XLA program: the
  per-operator expression evaluators compose by inlining each
  operator's bound references into its producer's expressions, so the
  whole stage evaluates straight off the input columns with no
  intermediate batch.
* `project/filter -> partial-agg-update` chains into the aggregate
  itself: `HashAggregateExec` grows a `pre_stage` whose composed
  predicates/outputs evaluate inside every update-lane kernel (sort,
  banded, dictionary, reduction) before grouping — scan-decode ->
  compute adjacency falls out of the same rule, since a chain sitting
  directly on a device scan fuses against the decoded columns.

Before compiling, the composed DAG runs `exprs/simplify.py` — peephole
rules (cross-operator constant folding, double-cast collapse) plus
common-subexpression dedup (`SharedExpr` slots evaluate once per
trace).  Compiled programs land in the shared `KernelCache` keyed by
the fused stage's structural fingerprint + batch signature, so repeat
collects and rebuilt plans hit warm executables.

Interop contracts preserved:

* per-node metrics: the fused node carries the stage totals and each
  member operator's MetricSet is charged a lazy per-member breakdown
  (rows after each fused filter ride the kernel's outputs as device
  scalars — no extra sync);
* OOM split-and-retry fires at fused-batch granularity
  (`TpuExec.oom_retry_batches` wraps every fused dispatch);
* watchdog compile deadlines cover fused compiles (kernels build
  through `KernelCache._build_watched`);
* deferred-selection/lazy batches pass through (a fused stage with
  filters emits a sparse mask exactly like `FilterExec`);
* EXPLAIN prints the fusion groups (member lines under the fused
  node; `utils/profile.py` renders the per-member metric breakdown).

Deopt: a stage containing an expression the fuser cannot compose
(ANSI-checked casts — their deferred-check row scoping differs under
composition — or any expression whose tree cannot be rewritten) is
left UNFUSED; a fused stage whose kernel fails to trace at runtime
deopts this exec to the per-operator lane and keeps going.  Only the
affected stage ever deopts, never the query.  Gate:
`spark.rapids.sql.fusion.enabled` (default on).

SPMD mode (`spark.rapids.sql.spmd.enabled`, exec/spmd.py): with the
gate on, the pass plans for whole-mesh execution instead of
per-partition dispatch — fusible chains stay standalone
`FusedStageExec` nodes (single-operator chains included: the SPMD lane
makes even a lone filter profitable, since one gang dispatch replaces
one dispatch per partition) rather than folding into the aggregate's
update lane, so the sharded stage program sees them.  At execution
time `FusedStageExec.execute_partitions` hands the stage to the SPMD
lane when a mesh is active; everything else (no mesh, unsupported
gang layouts, trace failure) deopts back to the per-partition lane
below.
"""
from __future__ import annotations

import functools
import logging
import time
from typing import Iterator, Optional

import jax.numpy as jnp

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.aggregate import AggMode, HashAggregateExec
from spark_rapids_tpu.exec.base import (
    TpuExec, UnaryExecBase, batch_signature, make_eval_context,
    named_jit)
from spark_rapids_tpu.exec.basic import FilterExec, ProjectExec, \
    _register_ansi
from spark_rapids_tpu.exprs.base import (
    BoundReference, EvalContext, Expression, fingerprint)
from spark_rapids_tpu.exprs.simplify import (
    dedup_common_subexprs, is_identity_projection, simplify)
from spark_rapids_tpu.utils import metrics as M

log = logging.getLogger("spark_rapids_tpu.plan.fusion")

#: execs whose batch functions are pure expression evaluation — the
#: only members a fused stage may contain.  Everything else (exchange,
#: coalesce, sort, join, AQE stage nodes) is a pipeline break.
_FUSIBLE = (ProjectExec, FilterExec)


class UnsupportedFusion(Exception):
    """A chain that must stay on the per-operator lane (the deopt)."""


# ---------------------------------------------------------------------------
# composition
def _contains_ansi(e: Expression) -> bool:
    """ANSI-checked expressions register deferred error checks whose
    row scoping would change under cross-filter composition — the one
    expression class the fuser refuses."""
    if getattr(e, "ansi", False):
        return True
    return any(_contains_ansi(c) for c in e.children())


def inline_refs(e: Expression, producers: list) -> Expression:
    """Substitute every BoundReference ordinal with the producing
    operator's expression for that column — the composition step that
    turns a two-operator pipeline into one DAG."""
    if isinstance(e, BoundReference):
        return producers[e.ordinal]
    return e.map_children(lambda c: inline_refs(c, producers))


class ComposedStage:
    """The composed form of one fusion group: output expressions and
    filter predicates over the BASE child's schema, plus the original
    member execs (names, metric sets, and the unfused deopt lane)."""

    def __init__(self, out_exprs, preds, schema, in_schema, members):
        self.out_exprs = list(out_exprs)
        self.preds = list(preds)
        self.schema = schema
        self.in_schema = in_schema
        self.members = list(members)  # original execs, bottom-up order

    @property
    def expr_count(self) -> int:
        return len(self.out_exprs) + len(self.preds)

    def member_names(self) -> list:
        return [type(m).__name__ for m in self.members]

    @functools.cached_property
    def kernel_label(self) -> str:
        """`fused-<n>-<members>`: the stage's kernel says it is a fused
        one and of what, and never carries a hash or a shape.  Built
        once a stage: `_kernel` runs per batch."""
        ops = [n.replace("Exec", "").lower() for n in self.member_names()]
        return "-".join(["fused", str(len(ops))] + ops)

    def fingerprint(self) -> tuple:
        return (fingerprint(self.out_exprs), fingerprint(self.preds),
                fingerprint(self.schema), fingerprint(self.in_schema))

    def describe_ops(self) -> str:
        return "→".join(n.replace("Exec", "")
                        for n in self.member_names())


def compose_chain(chain: list, in_schema: T.Schema) -> ComposedStage:
    """Compose a top-down Project/Filter chain into one ComposedStage
    over `in_schema`.  Raises UnsupportedFusion when any member carries
    an expression the fuser cannot compose."""
    members = list(reversed(chain))  # bottom-up execution order
    for ex in members:
        bound = ex._bound if isinstance(ex, ProjectExec) else [ex._bound]
        for e in bound:
            if _contains_ansi(e):
                raise UnsupportedFusion(
                    f"{type(ex).__name__} carries an ANSI-checked "
                    "expression")
    producers: list = [BoundReference(i, f.dtype)
                       for i, f in enumerate(in_schema.fields)]
    preds: list = []
    for ex in members:
        if isinstance(ex, ProjectExec):
            producers = [inline_refs(b, producers) for b in ex._bound]
        else:
            preds.append(inline_refs(ex._bound, producers))
    outs = [simplify(e) for e in producers]
    preds = [simplify(p) for p in preds]
    deduped = dedup_common_subexprs(preds + outs)
    preds, outs = deduped[:len(preds)], deduped[len(preds):]
    return ComposedStage(outs, preds, chain[0].output_schema(),
                         in_schema, members)


def _eval_stage(stage: ComposedStage, ctx: EvalContext):
    """Inside a kernel trace: evaluate the composed predicates (ANDing
    into the row mask, one running count per filter) then the composed
    outputs under the FINAL mask.  Returns (out ColumnVectors, final
    mask, per-filter counts)."""
    keep = ctx.row_mask
    counts = []
    for p in stage.preds:
        v = p.eval(ctx)
        keep = keep & v.validity & v.data.astype(bool)
        counts.append(keep.sum().astype(jnp.int32))
    octx = EvalContext(ctx.columns, ctx.capacity, ctx.num_rows, keep,
                       ctx.pending_checks, ctx.shared)
    cols = [e.eval(octx) for e in stage.out_exprs]
    return cols, keep, counts


def eval_stage_ctx(stage: ComposedStage, ctx: EvalContext) -> EvalContext:
    """The aggregate-update prologue: thread an EvalContext through a
    composed stage so the consuming kernel sees the post-stage columns
    and row mask — all inside the consumer's own jit."""
    cols, keep, _ = _eval_stage(stage, ctx)
    return EvalContext(cols, ctx.capacity, ctx.num_rows, keep,
                       ctx.pending_checks, ctx.shared)


# ---------------------------------------------------------------------------
class FusedStageExec(UnaryExecBase):
    """A fused Project/Filter chain: one jitted XLA program per batch
    signature evaluates the whole stage off the input columns.  With
    filter members the output rides a deferred-selection mask exactly
    like FilterExec; a pure-project stage passes the input's row count
    and sparse mask through."""

    def __init__(self, stage: ComposedStage, child: TpuExec):
        super().__init__(child)
        self.stage = stage
        self._schema = stage.schema
        self._fusion_deopt = False
        self._spmd_deopt = False

    def output_schema(self) -> T.Schema:
        return self._schema

    @property
    def coalesce_after(self) -> bool:
        # filters shrink batches; keep the downstream re-bucket
        return bool(self.stage.preds)

    @property
    def fused_members(self):
        """(describe, MetricSet) per member — the EXPLAIN-with-metrics
        breakdown (utils/profile.py renders these under the node)."""
        return [(m.describe(), m.metrics) for m in self.stage.members]

    def cache_scope(self):
        return self.stage.fingerprint()

    def describe(self):
        return (f"FusedStageExec({self.stage.describe_ops()}, "
                f"exprs={self.stage.expr_count}"
                + (", deopt" if self._fusion_deopt else "")
                + (", spmd-deopt" if self._spmd_deopt else "") + ")")

    def execute_partitions(self):
        # whole-mesh SPMD lane (exec/spmd.py): one sharded gang
        # dispatch for every partition of this stage when the conf
        # enables it and a mesh is active; None = per-partition lane
        from spark_rapids_tpu.exec import spmd as SP
        lane = SP.maybe_execute_spmd(self)
        if lane is not None:
            return lane
        return super().execute_partitions()

    def tree_string(self, indent: int = 0) -> str:
        # EXPLAIN prints the fusion group: one `* member` line per
        # fused operator, then the real children
        s = "  " * indent + self.describe()
        for m in self.stage.members:
            s += "\n" + "  " * (indent + 1) + "* " + m.describe()
        for c in self._children:
            s += "\n" + c.tree_string(indent + 1)
        return s

    # -- fused lane ----------------------------------------------------------
    def _kernel(self, batch: ColumnarBatch):
        key = ("fused-stage", batch_signature(batch))
        label = self.stage.kernel_label

        def build():
            stage = self.stage
            cap = batch.capacity
            has_filter = bool(stage.preds)
            labels: list = []

            @named_jit(label)
            def kernel(columns, num_rows, mask=None):
                ctx = make_eval_context(columns, cap, num_rows, mask)
                cols, keep, counts = _eval_stage(stage, ctx)
                labels.clear()
                labels.extend(l for l, _ in ctx.pending_checks)
                pend = tuple(f for _, f in ctx.pending_checks)
                if has_filter:
                    return cols, tuple(counts), keep, pend
                return cols, pend

            kernel._ansi_labels = labels
            kernel._has_filter = has_filter
            return kernel

        # fused kernels carry member attribution: the catalog entry
        # names the member operators this one program evaluates, so
        # the kernel table points back at the fused plan nodes
        return self.kernels.get_or_build(
            key, build,
            meta=self.kp_meta(label,
                              members=self.stage.member_names()))

    def _run_one(self, batch: ColumnarBatch) -> ColumnarBatch:
        from spark_rapids_tpu.utils import profile as P
        kern = self._kernel(batch)
        first = not getattr(kern, "_fused_reported", False)
        t0 = time.perf_counter() if first else 0.0
        if batch.sparse is not None:
            out = kern(batch.columns, batch.num_rows_i32, batch.sparse)
        else:
            out = kern(batch.columns, batch.num_rows_i32)
        if first:
            # a jit's first call traces + compiles synchronously, so
            # this delta IS the stage's compile cost
            kern._fused_reported = True
            P.event(P.EV_STAGE_FUSED,
                    members=self.stage.member_names(),
                    exprs=self.stage.expr_count,
                    compile_ms=round(
                        (time.perf_counter() - t0) * 1e3, 2))
        if kern._has_filter:
            cols, counts, keep, pend = out
            checks = batch.checks + _register_ansi(pend,
                                                   kern._ansi_labels)
            result = ColumnarBatch(self._schema, list(cols), counts[-1],
                                   checks, sparse=keep)
        else:
            cols, pend = out
            counts = ()
            checks = batch.checks + _register_ansi(pend,
                                                   kern._ansi_labels)
            result = ColumnarBatch(self._schema, list(cols), batch._rows,
                                   checks, batch.sparse)
        self._charge_members(batch, counts)
        self.update_output_metrics(result)
        return result

    def _charge_members(self, batch: ColumnarBatch, counts) -> None:
        """Per-member metric breakdown: rows after each fused filter
        come back as device scalars and queue LAZILY (MetricSet.add),
        so the breakdown costs no host sync."""
        ci = 0
        rows = batch._rows
        for m in self.stage.members:
            if isinstance(m, FilterExec) and ci < len(counts):
                rows = counts[ci]
                ci += 1
            m.metrics.add(M.NUM_OUTPUT_ROWS, rows)
            m.metrics.add(M.NUM_OUTPUT_BATCHES, 1)

    # -- deopt (unfused) lane ------------------------------------------------
    def _process_unfused(self, batches) -> Iterator[ColumnarBatch]:
        """Per-operator fallback: the original member execs' partition
        processors chained in execution order (they are partition-local
        and never touch their plan children)."""
        it = batches
        for m in self.stage.members:
            it = m.process_partition(it)
        for out in it:
            self.update_output_metrics(out)
            yield out

    def _deopt(self, err: BaseException) -> None:
        self._fusion_deopt = True
        self.metrics.add(M.NUM_FUSION_DEOPTS, 1)
        from spark_rapids_tpu.utils import profile as P
        P.event(P.EV_FUSION_DEOPT, members=self.stage.member_names(),
                error=f"{type(err).__name__}: {err}"[:300])
        log.warning(
            "fused stage [%s] failed to build/trace; deopting this "
            "stage to the per-operator lane: %s",
            self.stage.describe_ops(), err)

    def process_partition(self, batches) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.utils.watchdog import TpuQueryTimeout
        if self._fusion_deopt:
            yield from self._process_unfused(batches)
            return
        for batch in batches:
            if self._fusion_deopt:
                # a concurrent partition deopted mid-stream: finish
                # this partition unfused too
                yield from self._process_unfused(iter([batch]))
                continue
            try:
                with self.metrics.timed(M.TOTAL_TIME):
                    outs = list(self.oom_retry_batches(
                        batch, self._run_one,
                        label=f"FusedStage[{self.stage.describe_ops()}]"))
            except (MemoryError, TpuQueryTimeout):
                raise  # the OOM lattice / watchdog own these
            except Exception as e:  # noqa: BLE001 — unsupported-expr
                self._deopt(e)      # trace failures deopt THIS stage
                yield from self._process_unfused(iter([batch]))
                continue
            yield from outs


# ---------------------------------------------------------------------------
# the plan pass
def fuse_plan(plan, conf: Optional[C.RapidsConf] = None):
    """Entry point: fuse every TPU subtree of `plan` (a TpuExec, or a
    CpuNode tree with accelerated islands).  Identity when
    spark.rapids.sql.fusion.enabled is off.  With
    spark.rapids.sql.spmd.enabled the pass plans for whole-mesh
    execution: chains stay standalone FusedStageExec nodes (even
    single-operator runs) instead of folding into aggregate update
    lanes, so exec/spmd.py's gang dispatch sees them."""
    conf = conf or C.get_active_conf()
    if not conf[C.FUSION_ENABLED]:
        return plan
    spmd = bool(conf[C.SPMD_ENABLED])
    if isinstance(plan, TpuExec):
        return _fuse_node(plan, spmd)
    _fuse_islands(plan, spmd)
    return plan


def _fuse_islands(node, spmd: bool = False) -> None:
    from spark_rapids_tpu.plan.transitions import (ColumnarToRowExec,
                                                   RowToColumnarExec)
    if isinstance(node, ColumnarToRowExec):
        node.tpu_child = _fuse_node(node.tpu_child, spmd)
        return
    for c in getattr(node, "children", []):
        _fuse_islands(c, spmd)


def _fuse_tpu_islands(node: TpuExec, spmd: bool = False) -> None:
    from spark_rapids_tpu.plan.transitions import RowToColumnarExec
    if isinstance(node, RowToColumnarExec):
        _fuse_islands(node.cpu_child, spmd)


def _collect_chain(node: TpuExec):
    """Maximal Project/Filter chain from `node` down; returns
    (chain top-down, base child)."""
    chain: list = []
    cur = node
    while isinstance(cur, _FUSIBLE):
        chain.append(cur)
        cur = cur.child
    return chain, cur


def _agg_fusible(node: TpuExec) -> bool:
    return (isinstance(node, HashAggregateExec)
            and node.mode in (AggMode.PARTIAL, AggMode.COMPLETE)
            and getattr(node, "_pre_stage", None) is None)


def _member_fusible(ex: TpuExec) -> bool:
    bound = ex._bound if isinstance(ex, ProjectExec) else [ex._bound]
    return not any(_contains_ansi(e) for e in bound)


def _fuse_segment(run: list, base: TpuExec,
                  spmd: bool = False) -> Optional[TpuExec]:
    """Fuse one bottom-up run of fusible members over `base`; None when
    the segment must stay per-operator.  SPMD mode fuses even a lone
    operator: the gang dispatch amortizes over partitions, not over
    chain length."""
    try:
        stage = compose_chain(list(reversed(run)), base.output_schema())
    except Exception as e:  # noqa: BLE001 — per-stage deopt
        log.info("stage fusion skipped for [%s]: %s",
                 "→".join(type(x).__name__ for x in run), e)
        return None
    if not stage.preds and is_identity_projection(
            stage.out_exprs, stage.in_schema, stage.schema):
        return base  # the whole segment was a no-op projection
    if len(run) < 2 and not spmd:
        return None  # a lone operator gains nothing from fusing
    return FusedStageExec(stage, base)


def _fuse_chain(chain: list, base: TpuExec,
                spmd: bool = False) -> TpuExec:
    """Rebuild a top-down Project/Filter chain over `base`, fusing each
    maximal run of fusible members — a chain mixing supported and
    unsupported expressions fuses its supported runs and leaves only
    the unsupported members per-operator (the per-stage deopt)."""
    members = list(reversed(chain))  # bottom-up execution order
    cur = base
    i = 0
    while i < len(members):
        if _member_fusible(members[i]):
            j = i
            while j < len(members) and _member_fusible(members[j]):
                j += 1
            fused = _fuse_segment(members[i:j], cur, spmd)
            if fused is not None:
                cur = fused
                i = j
                continue
            # segment could not fuse: reattach its members one by one
            for m in members[i:j]:
                m._children[0] = cur
                cur = m
            i = j
        else:
            members[i]._children[0] = cur
            cur = members[i]
            i += 1
    return cur


def _fuse_node(node: TpuExec, spmd: bool = False) -> TpuExec:
    _fuse_tpu_islands(node, spmd)
    if _agg_fusible(node) and not spmd:
        # SPMD-capable stage detection: in SPMD mode the chain stays a
        # standalone FusedStageExec below (the gang program runs it
        # over the mesh; the aggregate's update lane then consumes the
        # sharded outputs per-partition) instead of folding into the
        # aggregate's update kernels
        chain, base = _collect_chain(node.child)
        if chain and all(_member_fusible(m) for m in chain):
            stage = None
            try:
                stage = compose_chain(chain, base.output_schema())
            except Exception as e:  # noqa: BLE001 — per-stage deopt:
                log.info("aggregate fusion skipped for [%s]: %s",
                         "→".join(type(x).__name__ for x in chain), e)
            if stage is not None:
                return HashAggregateExec(
                    node.group_exprs, node.aggregates,
                    _fuse_node(base, spmd), mode=node.mode,
                    pre_stage=stage)
            # fall through: the chain may still fuse standalone below
    if isinstance(node, _FUSIBLE):
        chain, base = _collect_chain(node)
        return _fuse_chain(chain, _fuse_node(base, spmd), spmd)
    for i, c in enumerate(node.children):
        node._children[i] = _fuse_node(c, spmd)
    return node
