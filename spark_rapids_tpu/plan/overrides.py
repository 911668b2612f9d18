"""Replacement-rule registry + the plan-rewrite entry point (reference
`GpuOverrides.scala`: `ReplacementRule` builders for expressions /
partitionings / execs, `GpuOverrides.apply` pre-pass and the
`GpuTransitionOverrides` post-pass).

`accelerate(cpu_plan, conf)` is the full pipeline:
  wrap -> tag (bottom-up) -> consistency fixups -> explain -> convert
  -> transitions (R2C/C2R bridges, coalesce insertion, pair elimination).

Conversion is *planning* too: aggregate rules expand to
partial -> exchange -> final (the shape Spark's planner produces before
the reference ever sees it), joins insert key exchanges or broadcast, and
global sorts become range-exchange + per-partition sort.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Callable, Optional, Sequence

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exec import basic as B
from spark_rapids_tpu.exec.aggregate import AggMode, HashAggregateExec
from spark_rapids_tpu.exec.base import TpuExec
from spark_rapids_tpu.exec.joins import (
    BroadcastHashJoinExec, HashJoinExec, JoinType, NestedLoopJoinExec)
from spark_rapids_tpu.exec.limit import GlobalLimitExec, LocalLimitExec
from spark_rapids_tpu.exec.sort import SortExec
from spark_rapids_tpu.exprs.base import Expression
from spark_rapids_tpu.plan import nodes as N
from spark_rapids_tpu.plan.meta import (
    PlanMeta, fix_up_exchange_overhead, wrap_plan)
from spark_rapids_tpu.shuffle.exchange import (
    BroadcastExchangeExec, ShuffleExchangeExec)
from spark_rapids_tpu.shuffle.partitioning import (
    HashPartitioning, RangePartitioning, RoundRobinPartitioning,
    SinglePartitioning)

log = logging.getLogger("spark_rapids_tpu.plan")


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class ExprRule:
    """Per-expression replacement rule (reference ReplacementRule).  Our
    Expression AST is shared between engines, so `convert` is identity —
    the rule carries tagging knowledge: docs, incompat notes, extra tag
    hooks."""
    name: str
    desc: str
    incompat: Optional[str] = None
    tag_extra: Optional[Callable] = None


@dataclasses.dataclass
class ExecRule:
    cpu_class: type
    desc: str
    convert: Callable[[PlanMeta, list[TpuExec]], TpuExec]
    exprs_of: Callable[[N.CpuNode], Sequence[Expression]] = lambda n: ()
    tag_extra: Optional[Callable] = None

    @property
    def name(self) -> str:
        return self.cpu_class.__name__


EXPR_RULES: dict[str, ExprRule] = {}
EXEC_RULES: dict[type, ExecRule] = {}


def expr(name: str, desc: str, incompat: Optional[str] = None,
         tag_extra=None) -> None:
    EXPR_RULES[name] = ExprRule(name, desc, incompat, tag_extra)


def register_exec(cpu_class, desc, convert, exprs_of=lambda n: (),
                  tag_extra=None) -> None:
    EXEC_RULES[cpu_class] = ExecRule(cpu_class, desc, convert, exprs_of,
                                     tag_extra)


def expr_rule_for(e: Expression) -> Optional[ExprRule]:
    return EXPR_RULES.get(type(e).__name__)


def exec_rule_for(node: N.CpuNode) -> Optional[ExecRule]:
    _ensure_io_rules()
    return EXEC_RULES.get(type(node))


# ---------------------------------------------------------------------------
# expression registry: every TPU expression class, with incompat markers
# mirroring the reference's (GpuOverrides.scala commonExpressions :491)
_SIMPLE_EXPRS = """
AttributeReference BoundReference Literal Alias
Add Subtract Multiply Divide IntegralDivide Remainder Pmod UnaryMinus
UnaryPositive Abs
EqualTo EqualNullSafe LessThan LessThanOrEqual GreaterThan
GreaterThanOrEqual And Or Not IsNull IsNotNull IsNaN InSet
BitwiseAnd BitwiseOr BitwiseXor BitwiseNot ShiftLeft ShiftRight
ShiftRightUnsigned
If CaseWhen Coalesce NullIf Nvl2 AtLeastNNonNulls NaNvl
Year Month DayOfMonth DayOfWeek DayOfYear Quarter WeekOfYear LastDay
Hour Minute Second DateAdd DateSub DateDiff AddMonths MonthsBetween
UnixTimestamp FromUnixTime ToDate TruncDate
Sqrt Cbrt Exp Expm1 Log Log1p Log2 Log10 Rint Signum Ceil Floor Pow Round
MonotonicallyIncreasingID SparkPartitionID
NormalizeNaNAndZero KnownFloatingPointNormalized KnownNotNull
Length Upper Lower InitCap Substring StringTrim StringTrimLeft
StringTrimRight ConcatStrings Contains StartsWith EndsWith Like
StringLocate StringReplace LPad RPad
Sum Count Min Max First Last
GroupRef
Logarithm WeekDay ToUnixTimestamp TimeAdd
""".split()
for _name in _SIMPLE_EXPRS:
    expr(_name, f"TPU implementation of {_name}")

# transcendentals differ in ulp from JVM StrictMath (reference marks these
# incompat the same way)
for _name in ("Sin", "Cos", "Tan", "Asin", "Acos", "Atan", "Sinh", "Cosh",
              "Tanh", "ToDegrees", "ToRadians", "Cot", "Acosh", "Asinh",
              "Atanh"):
    expr(_name, f"TPU implementation of {_name}",
         incompat="floating point results differ in ulp from the JVM")

expr("Rand", "per-row uniform random", incompat="TPU RNG stream differs "
     "from JVM XORShiftRandom")


def _tag_cast(m) -> None:
    """Per-direction cast gating (reference CastExprMeta, GpuCast.scala:31):
    the gated directions exist because device formatting/parsing is not
    bit-identical to the JVM; everything the kernels cannot do tags the
    plan for CPU fallback instead of raising at execution time."""
    e = m.expr
    src = None
    for schema in m.input_schemas():
        try:
            src = e.child.data_type(schema)
            break
        except Exception:
            continue
    if src is None:
        return  # unresolvable child type: leave to downstream tagging
    dst = e.to
    if getattr(e, "ansi", False):
        # ANSI numeric overflow checks are implemented (deferred-check
        # raise at the collect boundary, GpuCast.scala:188 analog);
        # other ANSI directions still fall back
        if not (src.is_numeric and dst.is_numeric and
                not dst.is_floating):
            m.will_not_work_on_tpu(
                "ANSI cast supported only for numeric -> integral "
                "overflow checks")
    if src.is_floating and dst.is_string and \
            not m.conf[C.CASTS_FLOAT_TO_STRING]:
        m.will_not_work_on_tpu(
            "float->string formatting differs from Java at extreme "
            f"exponents; enable with {C.CASTS_FLOAT_TO_STRING.key}")
    if src.is_string and dst.is_floating and \
            not m.conf[C.CASTS_STRING_TO_FLOAT]:
        m.will_not_work_on_tpu(
            "string->float parse may differ by 1 ulp from Java; enable "
            f"with {C.CASTS_STRING_TO_FLOAT.key}")
    if src.is_string and dst.id == T.TypeId.TIMESTAMP_US and \
            not m.conf[C.CASTS_STRING_TO_TS]:
        m.will_not_work_on_tpu(
            "string->timestamp supports canonical forms only; enable "
            f"with {C.CASTS_STRING_TO_TS.key}")


expr("Cast", "TPU implementation of Cast", tag_extra=_tag_cast)


def _tag_substring_index(m) -> None:
    d, n = m.expr.literal_args()
    if d is None or n is None:
        m.will_not_work_on_tpu(
            "substring_index delimiter and count must be literals")


expr("SubstringIndex", "TPU implementation of SubstringIndex",
     tag_extra=_tag_substring_index)


def _tag_string_split(m) -> None:
    """StringSplit is evaluable only as split(s,d)[i] with a literal,
    regex-free pattern and limit != 0 (reference GpuStringSplit +
    regexp-as-literal rule, stringFunctions.scala:812)."""
    e = m.expr
    parent = m.parent
    from spark_rapids_tpu.exprs.complex import GetArrayItem
    if not (hasattr(parent, "expr") and
            isinstance(parent.expr, GetArrayItem)):
        m.will_not_work_on_tpu(
            "split() result must be indexed (split(s,d)[i]); array "
            "columns are outside the v0 type matrix")
    if e.literal_pattern() is None:
        m.will_not_work_on_tpu(
            "split pattern must be a literal without regex "
            "metacharacters")
    if e.literal_limit() in (None, 0):
        m.will_not_work_on_tpu(
            "split limit must be a literal -1 or positive")


def _tag_inline_only(consumer_name, consumers):
    def tag(m):
        parent = m.parent
        if not (hasattr(parent, "expr") and
                isinstance(parent.expr, consumers)):
            m.will_not_work_on_tpu(
                f"{type(m.expr).__name__} must be consumed by "
                f"{consumer_name}; array/map columns are outside the v0 "
                "type matrix")
    return tag


def _tag_get_array_item(m) -> None:
    from spark_rapids_tpu.exprs.complex import CreateArray
    from spark_rapids_tpu.exprs.string_fns import StringSplit
    if not isinstance(m.expr.child, (CreateArray, StringSplit)):
        m.will_not_work_on_tpu(
            "GetArrayItem supports inline arrays (split()/array()) only")


def _tag_get_map_value(m) -> None:
    from spark_rapids_tpu.exprs.complex import CreateMap
    if not isinstance(m.expr.child, CreateMap):
        m.will_not_work_on_tpu(
            "GetMapValue supports inline map(...) only")


def _register_complex_rules():
    from spark_rapids_tpu.exprs.complex import (
        CreateArray, CreateMap, GetArrayItem, GetMapValue)
    from spark_rapids_tpu.exprs.string_fns import StringSplit
    expr("StringSplit", "split into parts, consumed by [] "
         "(fused split-part kernel)", tag_extra=_tag_string_split)
    expr("GetArrayItem", "index an inline array",
         tag_extra=_tag_get_array_item)
    expr("GetMapValue", "look up an inline map",
         tag_extra=_tag_get_map_value)
    expr("CreateArray", "inline array constructor",
         tag_extra=_tag_inline_only("GetArrayItem or explode",
                                    (GetArrayItem,)))
    expr("CreateMap", "inline map constructor",
         tag_extra=_tag_inline_only("GetMapValue", (GetMapValue,)))


_register_complex_rules()


expr("Average", "TPU average")

# single source of truth with the CPU engine's aggregate table
_SUPPORTED_AGGS = set(N._AGG_PANDAS)


def _tag_aggregate(meta) -> None:
    """Aggregate-function checks (reference GpuHashAggregateMeta tagging:
    registry membership + float-order-variance gating via
    spark.rapids.sql.variableFloatAgg.enabled)."""
    node = meta.node
    child_schema = node.child.output_schema()
    for a in node.aggregates:
        fname = type(a.func).__name__
        if fname not in _SUPPORTED_AGGS:
            meta.will_not_work_on_tpu(
                f"aggregate function {fname} has no TPU implementation")
            continue
        if fname in ("Average", "Sum", "StddevSamp",
                     "VarianceSamp") and not meta.conf[
                C.VARIABLE_FLOAT_AGG] and a.func.child is not None:
            try:
                dt = a.func.child.data_type(child_schema)
            except Exception:
                continue
            if dt.is_floating:
                meta.will_not_work_on_tpu(
                    f"float {fname} varies with evaluation order; enable "
                    f"with {C.VARIABLE_FLOAT_AGG.key}")


# ---------------------------------------------------------------------------
# exec converters
def _conv_source(meta, kids) -> TpuExec:
    node: N.CpuSource = meta.node
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.plan.transitions import host_columns_from_df
    from spark_rapids_tpu.utils import movement as MV
    from spark_rapids_tpu.utils import profile as P
    # chunk at the upload boundary like every other source (transitions
    # RowToColumnarExec / HostColumnarToDeviceExec): device batch
    # capacities stay in batchMaxRows' bounded bucket set.  Whole
    # partitions as one batch made an SF1 lineitem partition a 4M-row
    # kernel shape, and XLA:TPU compile time grows steeply with it (q6's
    # reduce kernel: past the 300 s task watchdog on the v5e's host).
    #
    # The batches are chunks; the TRANSFERS are not.  A host-to-device
    # call costs about a quarter of a millisecond on the v5e whatever it
    # carries, so a partition is converted once and its fixed-width
    # columns go to the device whole, the fixed-width ones in one
    # `device_put` and the string ones (encoded from their Arrow
    # buffers, never a Python object a value) in a second; a jitted
    # split program cuts them there into the batches a chunk-by-chunk
    # `from_numpy` would give (`ColumnarBatch.chunks_from_numpy`: a run
    # under two full chunks still goes chunk by chunk).
    max_rows = meta.conf[C.MAX_BATCH_ROWS]
    schema = node.output_schema()
    # the upload is eager and most of a hot scan query's wall: one span
    # per source around all of it and, per partition, one around the
    # host conversion and one around the device_puts and the split
    # (the operator ranges of the reference's HostColumnarToGpu).  Per
    # partition, not per chunk: a trace reader that explains every idle
    # gap of the device by the spans around it pays for each span.
    # `transfers` counts the host-to-device arrays sent
    tr = P.tracer()
    label = "" if tr is None else \
        f"{P.SPAN_SOURCE_UPLOAD}[s{tr.ordinal(P.SPAN_SOURCE_UPLOAD)}]"
    parts, nbytes, transfers = [], 0, 0
    # one partition a chip: under an active mesh of as many chips as the
    # source has partitions, partition p's transfers and its split
    # program go to chip p
    from spark_rapids_tpu.parallel import mesh as PM
    chips = PM.partition_devices(len(node.partitions))
    with P.span(label) as upload:
        for p, df in enumerate(node.partitions):
            nchunks = -(-len(df) // max_rows)
            with P.span(P.SPAN_UPLOAD_CONVERT, partition=p,
                        chunks=nchunks, rows=len(df)):
                data, validity = host_columns_from_df(df, schema)
            with P.span(P.SPAN_UPLOAD_PUT, partition=p,
                        chunks=nchunks, rows=len(df)) as put:
                chunks, sent = ColumnarBatch.chunks_from_numpy(
                    data, schema, validity, max_rows,
                    device=chips[p] if chips else None)
                if put is not None:
                    put.args["device_bytes"] = b = sum(
                        MV.vector_device_bytes(col)
                        for batch in chunks for col in batch.columns)
                    put.args["transfers"] = sent
                    nbytes += b
                    transfers += sent
            parts.append(chunks)
        if upload is not None:
            upload.args = {"partitions": len(parts),
                           "batches": sum(map(len, parts)),
                           "rows": sum(map(len, node.partitions)),
                           "device_bytes": nbytes,
                           "transfers": transfers}
    src = B.LocalBatchSource(parts, node.output_schema())
    # stable identity across plan rebuilds: the uploaded device batches
    # are fresh per accelerate(), but the backing pandas partitions are
    # the session's long-lived objects — the result cache keys on THEM
    # so a dashboard re-running the same query over the same sources
    # hits even though each run re-plans
    src.source_identity = getattr(node, "source_identity", None) \
        or tuple(node.partitions)
    return src


def _conv_range(meta, kids) -> TpuExec:
    node: N.CpuRange = meta.node
    return B.RangeExec(node.start, node.end, node.step,
                       num_partitions=node.num_partitions)


def _conv_project(meta, kids) -> TpuExec:
    return B.ProjectExec(meta.node.exprs, kids[0])


def _conv_filter(meta, kids) -> TpuExec:
    # filter-over-scan: push the predicate into the scan for row-group
    # pruning (Spark pushed-filters shape); the FilterExec stays for
    # exactness (stats pruning is conservative, not exact)
    from spark_rapids_tpu.io.exec import TpuFileSourceScanExec
    if isinstance(kids[0], TpuFileSourceScanExec) and \
            kids[0].pushed_filter is None:
        kids[0].pushed_filter = meta.node.condition
    return B.FilterExec(meta.node.condition, kids[0])


def _conv_union(meta, kids) -> TpuExec:
    return B.UnionExec(*kids)


def _conv_limit(meta, kids) -> TpuExec:
    node: N.CpuLimit = meta.node
    child = kids[0]
    if node.global_limit:
        # ORDER BY + LIMIT -> top-N (Spark plans this shape as
        # TakeOrderedAndProjectExec; our SortedTopNExec prunes each
        # batch to n candidates — top_k fast path for single numeric
        # keys — and re-sorts the merged candidates exactly)
        from spark_rapids_tpu.exec.sort import SortedTopNExec
        if (isinstance(child, SortExec) and child.global_sort and
                node.n <= 1 << 14):
            src = child.child
            if (isinstance(src, ShuffleExchangeExec) and
                    isinstance(src.partitioning, RangePartitioning)):
                # the range exchange only existed to totally order the
                # partitions; top-N prunes per partition instead
                src = src.child
            return SortedTopNExec(node.n, child.order, src)
        return GlobalLimitExec(node.n, LocalLimitExec(node.n, child))
    return LocalLimitExec(node.n, child)


def _conv_sort(meta, kids) -> TpuExec:
    node: N.CpuSort = meta.node
    if not node.global_sort:
        return SortExec(node.order, kids[0], global_sort=False)
    nparts = _num_partitions_of(kids[0])
    if nparts > 1:
        # total order: range-exchange then per-partition sort (the shape
        # Spark's planner + reference produce for global sorts)
        ex = ShuffleExchangeExec(
            RangePartitioning(node.order, nparts), kids[0])
        return SortExec(node.order, ex, global_sort=True)
    return SortExec(node.order, kids[0], global_sort=True)


def _num_partitions_of(plan: TpuExec) -> int:
    return plan.output_partition_count()


def _exchange_partitions(nparts: int, conf: C.RapidsConf) -> int:
    """Partition count for a planned hash exchange.  When the mesh ICI
    exchange lane is active (conf + set_active_mesh), plan at the mesh
    size so each device owns exactly one output partition and the
    exchange routes through the all-to-all collective
    (ShuffleExchangeExec._mesh_routable)."""
    from spark_rapids_tpu.parallel import mesh as PM
    active = PM.get_active_mesh()
    if active is not None and conf[C.MESH_EXCHANGE_ENABLED]:
        mesh, axis = active
        return mesh.shape[axis]
    return nparts


def _plain_column(e: Expression, schema: T.Schema) -> Optional[str]:
    """The name of the column `e` hands on unchanged, or None."""
    from spark_rapids_tpu.exprs.base import (
        Alias, AttributeReference, BoundReference)
    if isinstance(e, Alias):
        inner = _plain_column(e.child, schema)
        return inner if inner == e.name else None
    if isinstance(e, AttributeReference):
        return e.name
    if isinstance(e, BoundReference):
        return schema.fields[e.ordinal].name
    return None


def _clustered_on(plan: TpuExec) -> list[frozenset]:
    """Sets of `plan`'s output columns such that rows equal on a set lie
    in ONE of its partitions (Spark's outputPartitioning, as far as the
    planner needs it): a hash exchange's keys; a co-partitioned join's
    keys on the sides it keeps whole (an outer join's null-extended
    side is spread); handed up through the operators that keep rows in
    their partition and those columns under their names.  A name the
    schema holds twice says nothing."""
    schema = plan.output_schema()
    names = [f.name for f in schema.fields]

    def named(exprs, side_schema):
        cols = [_plain_column(e, side_schema) for e in exprs]
        if cols and all(c is not None and names.count(c) == 1
                        for c in cols):
            return [frozenset(cols)]
        return []
    if isinstance(plan, ShuffleExchangeExec):
        if isinstance(plan.partitioning, HashPartitioning) \
                and not plan.coalesce_small:
            return named(plan.partitioning.exprs, schema)
        return []
    if isinstance(plan, HashJoinExec):
        if plan.co_partitions() is None:
            return []
        left = named(plan.left_keys, plan.children[0].output_schema())
        right = named(plan.right_keys, plan.children[1].output_schema())
        return {JoinType.INNER: left + right,
                JoinType.LEFT_OUTER: left, JoinType.LEFT_SEMI: left,
                JoinType.LEFT_ANTI: left,
                JoinType.RIGHT_OUTER: right}.get(plan.join_type, [])
    if isinstance(plan, B.FilterExec):
        return _clustered_on(plan.child)
    if isinstance(plan, B.ProjectExec):
        kept = {_plain_column(e, plan.child.output_schema())
                for e in plan.exprs}
        return [k for k in _clustered_on(plan.child)
                if k <= kept and all(names.count(c) == 1 for c in k)]
    return []


def _conv_aggregate(meta, kids) -> TpuExec:
    node: N.CpuAggregate = meta.node
    child = kids[0]
    nparts = _num_partitions_of(child)
    if nparts <= 1:
        return HashAggregateExec(node.group_exprs, node.aggregates, child,
                                 AggMode.COMPLETE)
    # the child's partitions are group-disjoint where the group keys
    # contain its partitioning key (q3 groups on `l_orderkey`, the key of
    # the join below): each partition aggregates completely where it is
    groups = {_plain_column(e, child.output_schema())
              for e in node.group_exprs}
    if any(keys <= groups for keys in _clustered_on(child)):
        return HashAggregateExec(node.group_exprs, node.aggregates, child,
                                 AggMode.COMPLETE)
    # distributed: partial -> key exchange -> final (Spark planner shape;
    # reference GpuHashAggregateMeta handles each stage)
    partial = HashAggregateExec(node.group_exprs, node.aggregates, child,
                                AggMode.PARTIAL)
    if node.group_exprs:
        from spark_rapids_tpu.exprs.base import col
        keys = [col(f.name) for f in
                partial.output_schema().fields[:len(node.group_exprs)]]
        # coalesce_small: a final aggregation needs key clustering only,
        # so a small partial output skips the split kernels entirely
        ex = ShuffleExchangeExec(
            HashPartitioning(keys, _exchange_partitions(nparts, meta.conf)),
            partial, coalesce_small=True)
    else:
        ex = ShuffleExchangeExec(SinglePartitioning(), partial)
    return HashAggregateExec(
        [_group_ref(i, partial.output_schema())
         for i in range(len(node.group_exprs))],
        node.aggregates, ex, AggMode.FINAL)


def _group_ref(i, partial_schema):
    from spark_rapids_tpu.exprs.base import col, Alias
    f = partial_schema.fields[i]
    return Alias(col(f.name), f.name)


def _conv_hash_join(meta, kids) -> TpuExec:
    node: N.CpuHashJoin = meta.node
    left, right = kids
    if node.broadcast:
        from spark_rapids_tpu.shims import current_shims
        bex = current_shims(meta.conf).make_broadcast_exchange(right)
        return BroadcastHashJoinExec(node.join_type, node.left_keys,
                                     node.right_keys, left, bex,
                                     node.condition)
    nparts = max(_num_partitions_of(left), _num_partitions_of(right))
    if nparts > 1:
        nparts = _exchange_partitions(nparts, meta.conf)
        left = ShuffleExchangeExec(
            HashPartitioning(node.left_keys, nparts), left)
        right = ShuffleExchangeExec(
            HashPartitioning(node.right_keys, nparts), right)
    return HashJoinExec(node.join_type, node.left_keys, node.right_keys,
                        left, right, node.condition)


def _conv_nested_loop_join(meta, kids) -> TpuExec:
    node: N.CpuNestedLoopJoin = meta.node
    from spark_rapids_tpu.shims import current_shims
    shims = current_shims(meta.conf)
    return shims.make_nested_loop_join(
        node.join_type, kids[0], kids[1], node.condition,
        target_size_bytes=int(meta.conf[C.BATCH_SIZE_BYTES]))


def _tag_nested_loop_join(meta) -> None:
    """Reference `GpuOverrides.scala:1770-1789`: both brute-force join
    rules are disabled by default ('large joins can cause out of
    memory errors'); `GpuBroadcastNestedLoopJoinExec.scala:49-53`
    supports inner-like types only in v0.2."""
    node: N.CpuNestedLoopJoin = meta.node
    name = type(node).__name__
    if not meta.conf.is_op_enabled("exec", name, default=False):
        meta.will_not_work_on_tpu(
            f"{name} is disabled by default (large joins can cause out "
            f"of memory errors); enable with "
            f"{C.op_enable_key('exec', name)}")
    if node.join_type not in (JoinType.INNER, JoinType.CROSS):
        meta.will_not_work_on_tpu(
            f"nested loop join type {node.join_type} is not supported "
            f"on TPU (inner-like only)")


def _tag_join(meta) -> None:
    node: N.CpuHashJoin = meta.node
    supported = {JoinType.INNER, JoinType.LEFT_OUTER, JoinType.RIGHT_OUTER,
                 JoinType.FULL_OUTER, JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                 JoinType.CROSS}
    if node.join_type not in supported:
        meta.will_not_work_on_tpu(
            f"join type {node.join_type} not supported on TPU")
    if node.condition is not None and node.join_type not in (
            JoinType.INNER, JoinType.CROSS):
        meta.will_not_work_on_tpu(
            "residual join condition only supported for inner joins")


def _strip_smj_sort(kid: TpuExec, keys) -> TpuExec:
    """Drop a per-partition SortExec that EXACTLY matches the ordering a
    sort-merge join would have required (ascending join keys, in key
    order, default null ordering) — that sort only existed to feed the
    SMJ we are replacing (reference GpuSortMergeJoinExec.scala:40-52).
    Anything else — a user's explicit descending/reordered
    sortWithinPartitions — is kept (ADVICE r2)."""
    from spark_rapids_tpu.exprs.base import fingerprint
    if not isinstance(kid, SortExec) or kid.global_sort:
        return kid
    if len(kid.order) != len(keys):
        return kid
    for o, k in zip(kid.order, keys):
        if (not o.ascending or not o.resolved_nulls_first or
                fingerprint(o.expr) != fingerprint(k)):
            return kid
    return kid.child


def _conv_sort_merge_join(meta, kids) -> TpuExec:
    node: N.CpuSortMergeJoin = meta.node
    kids = [_strip_smj_sort(kids[0], node.left_keys),
            _strip_smj_sort(kids[1], node.right_keys)]
    return _conv_hash_join(meta, kids)


def _tag_sort_merge_join(meta) -> None:
    _tag_join(meta)
    if not meta.conf[C.REPLACE_SORT_MERGE_JOIN]:
        meta.will_not_work_on_tpu(
            "replacing SortMergeJoin disabled by "
            f"{C.REPLACE_SORT_MERGE_JOIN.key}")


_PART_OF_SPEC = {
    "hash": lambda s: HashPartitioning(list(s.exprs), s.num_partitions),
    "roundrobin": lambda s: RoundRobinPartitioning(s.num_partitions),
    "single": lambda s: SinglePartitioning(),
    "range": lambda s: RangePartitioning(list(s.order), s.num_partitions),
}


def _conv_shuffle(meta, kids) -> TpuExec:
    node: N.CpuShuffleExchange = meta.node
    from spark_rapids_tpu.shims import current_shims
    # user-requested repartitions keep their partition count under 3.1's
    # ShuffleExchangeLike contract (constructor drift routes via shims)
    return current_shims(meta.conf).make_shuffle_exchange(
        _PART_OF_SPEC[node.spec.kind](node.spec), kids[0],
        can_change_num_partitions=not node.user_specified)


def _conv_broadcast(meta, kids) -> TpuExec:
    from spark_rapids_tpu.shims import current_shims
    return current_shims(meta.conf).make_broadcast_exchange(kids[0])


register_exec(N.CpuSource, "in-memory source", _conv_source)
register_exec(N.CpuRange, "range generation", _conv_range)
register_exec(N.CpuProject, "projection", _conv_project,
              exprs_of=lambda n: n.exprs)
register_exec(N.CpuFilter, "filtering", _conv_filter,
              exprs_of=lambda n: [n.condition])
register_exec(N.CpuUnion, "union all", _conv_union)
register_exec(N.CpuLimit, "row limit", _conv_limit)
register_exec(N.CpuSort, "sorting", _conv_sort,
              exprs_of=lambda n: [o.expr for o in n.order])
register_exec(
    N.CpuAggregate, "hash aggregation", _conv_aggregate,
    exprs_of=lambda n: list(n.group_exprs) + [
        a.func.child for a in n.aggregates if a.func.child is not None],
    tag_extra=_tag_aggregate)
# sort-based aggregation converts to the SAME hash aggregate, matching
# the reference's exec[SortAggregateExec] -> GpuHashAggregateExec rule
# (GpuOverrides.scala: "the Gpu version always uses hash aggregation")
register_exec(
    N.CpuSortAggregate, "sort aggregation (replaced with hash agg)",
    _conv_aggregate,
    exprs_of=lambda n: list(n.group_exprs) + [
        a.func.child for a in n.aggregates if a.func.child is not None],
    tag_extra=_tag_aggregate)
register_exec(
    N.CpuHashJoin, "hash join", _conv_hash_join,
    exprs_of=lambda n: list(n.left_keys) + list(n.right_keys) +
    ([n.condition] if n.condition is not None else []),
    tag_extra=_tag_join)
# brute-force joins: registered like the reference's
# exec[BroadcastNestedLoopJoinExec] / exec[CartesianProductExec]
# pair (GpuOverrides.scala:1770-1789), both disabled by default
register_exec(
    N.CpuNestedLoopJoin, "join using brute force",
    _conv_nested_loop_join,
    exprs_of=lambda n: [n.condition] if n.condition is not None else [],
    tag_extra=_tag_nested_loop_join)
register_exec(
    N.CpuCartesianProduct, "cartesian product using brute force",
    _conv_nested_loop_join,
    exprs_of=lambda n: [n.condition] if n.condition is not None else [],
    tag_extra=_tag_nested_loop_join)
def _conv_cached_columnar(meta, kids) -> TpuExec:
    from spark_rapids_tpu.plan.transitions import HostColumnarToDeviceExec
    return HostColumnarToDeviceExec(meta.node)


def _conv_expand(meta, kids) -> TpuExec:
    from spark_rapids_tpu.exec.expand import ExpandExec
    node: N.CpuExpand = meta.node
    return ExpandExec(node.projections, list(node.names), kids[0])


def _conv_generate(meta, kids) -> TpuExec:
    from spark_rapids_tpu.exec.expand import GenerateExec
    node: N.CpuGenerate = meta.node
    return GenerateExec(node.element_exprs, kids[0],
                        include_pos=node.include_pos,
                        value_name=node.value_name,
                        retained=node.retained)


register_exec(
    N.CpuCachedColumnar, "host-columnar cache upload (HostColumnarToGpu)",
    _conv_cached_columnar)
register_exec(
    N.CpuExpand, "expand (grouping sets/rollup/cube)", _conv_expand,
    exprs_of=lambda n: [e for p in n.projections for e in p])
register_exec(
    N.CpuGenerate, "generate (inline-array explode)", _conv_generate,
    exprs_of=lambda n: list(n.element_exprs))
register_exec(
    N.CpuSortMergeJoin, "sort-merge join (replaced with hash join)",
    _conv_sort_merge_join,
    exprs_of=lambda n: list(n.left_keys) + list(n.right_keys) +
    ([n.condition] if n.condition is not None else []),
    tag_extra=_tag_sort_merge_join)
register_exec(N.CpuShuffleExchange, "shuffle exchange", _conv_shuffle,
              exprs_of=lambda n: list(n.spec.exprs) +
              [o.expr for o in n.spec.order])
register_exec(N.CpuBroadcastExchange, "broadcast exchange", _conv_broadcast)


# --- I/O (reference GpuOverrides scan rules + GpuReadXFileFormat checks) ----
_FORMAT_ENABLES = {
    "parquet": (C.PARQUET_ENABLED, C.PARQUET_READ_ENABLED,
                C.PARQUET_WRITE_ENABLED),
    "orc": (C.ORC_ENABLED, C.ORC_READ_ENABLED, C.ORC_WRITE_ENABLED),
    "csv": (C.CSV_ENABLED, C.CSV_READ_ENABLED, None),
}


def _tag_file_scan(meta) -> None:
    node = meta.node
    fmt = node.scan.file_format
    fmt_conf, read_conf, _ = _FORMAT_ENABLES[fmt]
    if not meta.conf[fmt_conf]:
        meta.will_not_work_on_tpu(
            f"{fmt} acceleration disabled by {fmt_conf.key}")
    elif not meta.conf[read_conf]:
        meta.will_not_work_on_tpu(
            f"{fmt} reads disabled by {read_conf.key}")
    if fmt == "csv":
        for reason in node.scan.reader.options.tag_unsupported():
            meta.will_not_work_on_tpu(f"CSV: {reason}")
    if fmt == "parquet":
        # hybrid-calendar (julian/gregorian) rebase is CPU-only: the CPU
        # fallback engine performs the actual Julian rebase (io/rebase.py)
        # while EXCEPTION/CORRECTED stay accelerated (reference
        # GpuParquetScan.scala:151-158,1108-1115); the conf key is
        # version-variant, so it routes through the shim layer
        from spark_rapids_tpu.io import rebase as RB
        from spark_rapids_tpu.shims import current_shims
        shims = current_shims(meta.conf)
        key = shims.parquet_rebase_read_key()
        mode = shims.parquet_rebase_read_mode(meta.conf)
        if mode == "LEGACY":
            meta.will_not_work_on_tpu(
                f"legacy datetime rebase requested via {key}")
        elif mode not in RB.READ_MODES:
            meta.will_not_work_on_tpu(
                f"{mode} is not a supported read rebase mode")


def _conv_file_scan(meta, kids) -> TpuExec:
    from spark_rapids_tpu.io.exec import TpuFileSourceScanExec
    return TpuFileSourceScanExec(meta.node.scan, meta.node.pushed_filter,
                                 meta.conf)


def _tag_write_files(meta) -> None:
    node = meta.node
    if node.file_format not in ("parquet", "orc"):
        meta.will_not_work_on_tpu(
            f"{node.file_format} writes have no TPU implementation")
        return
    fmt_conf, _, write_conf = _FORMAT_ENABLES[node.file_format]
    if not meta.conf[fmt_conf]:
        meta.will_not_work_on_tpu(
            f"{node.file_format} acceleration disabled by {fmt_conf.key}")
    elif not meta.conf[write_conf]:
        meta.will_not_work_on_tpu(
            f"{node.file_format} writes disabled by {write_conf.key}")
    if node.file_format == "parquet":
        # LEGACY rebase writes stay on the CPU engine, which performs the
        # Gregorian->Julian rebase (reference GpuParquetFileFormat.scala:83)
        from spark_rapids_tpu.io import rebase as RB
        from spark_rapids_tpu.shims import current_shims
        shims = current_shims(meta.conf)
        key = shims.parquet_rebase_write_key()
        mode = shims.parquet_rebase_write_mode(meta.conf)
        if mode == "LEGACY":
            meta.will_not_work_on_tpu(
                "LEGACY rebase mode for dates and timestamps "
                f"requested via {key}")
        elif mode not in RB.READ_MODES:
            meta.will_not_work_on_tpu(
                f"{mode} is not a supported write rebase mode")


def _conv_write_files(meta, kids) -> TpuExec:
    import copy
    from spark_rapids_tpu.io.exec import TpuWriteFilesExec
    node = meta.node
    if node.file_format == "parquet":
        # freeze the session's rebase mode into the writer options so
        # execution doesn't depend on the active conf at run time
        import dataclasses
        from spark_rapids_tpu.io import rebase as RB
        from spark_rapids_tpu.io.parquet import ParquetWriterOptions
        from spark_rapids_tpu.shims import current_shims
        opts = node.options or ParquetWriterOptions()
        if opts.rebase_mode is None:
            mode = current_shims(meta.conf).parquet_rebase_write_mode(
                meta.conf)
            node = copy.copy(node)
            node.options = dataclasses.replace(opts, rebase_mode=mode)
    return TpuWriteFilesExec(node, kids[0])


_io_rules_registered = False


def _ensure_io_rules() -> None:
    """Lazy registration: io.exec imports plan.nodes, so importing it at
    module load would be circular through plan/__init__."""
    global _io_rules_registered
    if _io_rules_registered:
        return
    _io_rules_registered = True
    from spark_rapids_tpu.io.exec import CpuFileScan, CpuWriteFiles
    register_exec(CpuFileScan, "columnar file scan", _conv_file_scan,
                  tag_extra=_tag_file_scan)
    register_exec(CpuWriteFiles, "columnar file write", _conv_write_files,
                  tag_extra=_tag_write_files)
    _register_pyudf_rules()
    _register_window_rule()


def _register_window_rule() -> None:
    from spark_rapids_tpu.exec.window import CpuWindow, WindowExec

    def _conv_window(meta, kids):
        # co-locate each window partition group (Spark plans a hash
        # exchange on the partition keys below WindowExec)
        child = kids[0]
        nparts = _num_partitions_of(child)
        if nparts > 1:
            if meta.node.spec.partition_by:
                # window eval needs partition-key clustering only
                child = ShuffleExchangeExec(
                    HashPartitioning(list(meta.node.spec.partition_by),
                                     nparts), child, coalesce_small=True)
            else:
                child = ShuffleExchangeExec(SinglePartitioning(), child)
        return WindowExec(meta.node.window_exprs, meta.node.spec, child)

    def _tag_window(meta) -> None:
        # reference GpuWindowExec tags unsupported frame shapes so they
        # fall back instead of crashing at kernel build
        node = meta.node
        child_schema = node.child.output_schema()
        if not node.spec.frame.is_rows:
            if len(node.spec.order_by) != 1:
                meta.will_not_work_on_tpu(
                    "range frames need exactly one order key on the TPU")
            else:
                # the kernel reads the order key as int64: reject
                # float/string keys so they fall back instead of being
                # silently truncated into peers
                try:
                    dt = node.spec.order_by[0].expr.data_type(
                        child_schema)
                except Exception:
                    dt = None
                if dt is not None and not dt.is_integral:
                    meta.will_not_work_on_tpu(
                        f"range frame order key must be integral/"
                        f"date/timestamp, got {dt}")
        for fn, _ in node.window_exprs:
            if fn.kind not in ("row_number", "rank", "dense_rank",
                               "lead", "lag", "sum", "min", "max",
                               "count", "avg", "first", "last"):
                meta.will_not_work_on_tpu(
                    f"window function {fn.kind} has no TPU "
                    "implementation")
            elif fn.kind in ("min", "max") and fn.child is not None:
                try:
                    dt = fn.child.data_type(child_schema)
                except Exception:
                    continue
                if dt.is_string:
                    meta.will_not_work_on_tpu(
                        "string window min/max has no TPU kernel")

    register_exec(
        CpuWindow, "window aggregation", _conv_window,
        exprs_of=lambda n: (
            [fn.child for fn, _ in n.window_exprs
             if fn.child is not None]
            + list(n.spec.partition_by)
            + [o.expr for o in n.spec.order_by]),
        tag_extra=_tag_window)


def _tag_pandas_exec(meta) -> None:
    # disabled by default (reference GpuOverrides.scala:1821-1845): the
    # per-exec enable key must be set explicitly
    name = meta.node.name()
    if not meta.conf.is_op_enabled("exec", name, default=False):
        meta.will_not_work_on_tpu(
            f"{name} is disabled by default; enable with "
            f"{C.op_enable_key('exec', name)}")


def _register_pyudf_rules() -> None:
    from spark_rapids_tpu.pyudf.exec import (
        AggregateInPandasExec, ArrowEvalPythonExec, CpuAggregateInPandas,
        CpuArrowEvalPython, CpuFlatMapCoGroupsInPandas,
        CpuFlatMapGroupsInPandas, CpuMapInPandas, CpuWindowInPandas,
        FlatMapCoGroupsInPandasExec, FlatMapGroupsInPandasExec,
        MapInPandasExec, WindowInPandasExec)
    register_exec(
        CpuArrowEvalPython, "vectorized python UDF evaluation",
        lambda meta, kids: ArrowEvalPythonExec(meta.node.udfs, kids[0]),
        exprs_of=lambda n: [a for u in n.udfs for a in u.args],
        tag_extra=_tag_pandas_exec)
    register_exec(
        CpuMapInPandas, "mapInPandas",
        lambda meta, kids: MapInPandasExec(meta.node, kids[0]),
        tag_extra=_tag_pandas_exec)
    register_exec(
        CpuFlatMapGroupsInPandas, "grouped applyInPandas",
        lambda meta, kids: FlatMapGroupsInPandasExec(meta.node, kids[0]),
        tag_extra=_tag_pandas_exec)
    register_exec(
        CpuAggregateInPandas, "grouped aggregate pandas UDF",
        lambda meta, kids: AggregateInPandasExec(meta.node, kids[0]),
        exprs_of=lambda n: [a for u in n.udfs for a in u.args],
        tag_extra=_tag_pandas_exec)
    register_exec(
        CpuWindowInPandas, "window pandas UDF",
        lambda meta, kids: WindowInPandasExec(meta.node, kids[0]),
        exprs_of=lambda n: [a for u in n.udfs for a in u.args],
        tag_extra=_tag_pandas_exec)
    register_exec(
        CpuFlatMapCoGroupsInPandas, "cogrouped applyInPandas",
        lambda meta, kids: FlatMapCoGroupsInPandasExec(
            meta.node, kids[0], kids[1]),
        tag_extra=_tag_pandas_exec)


# ---------------------------------------------------------------------------
class ExecutionPlanCapture:
    """Captures the most recent accelerated plan so tests can assert plan
    shape / fallback (reference ExecutionPlanCaptureCallback
    Plugin.scala:148-237)."""

    last_plan = None
    last_meta: Optional[PlanMeta] = None

    @classmethod
    def assert_did_fall_back(cls, op_name: str) -> None:
        assert cls.last_plan is not None, "no plan captured"
        found = _find_cpu_node(cls.last_plan, op_name)
        assert found, (f"expected {op_name} to fall back to CPU:\n"
                       f"{cls.last_plan}")

    @classmethod
    def assert_contains_tpu(cls, exec_name: str) -> None:
        assert cls.last_plan is not None, "no plan captured"
        assert _find_tpu_node(cls.last_plan, exec_name), (
            f"expected {exec_name} on TPU:\n{cls.last_plan}")


def _find_cpu_node(plan, name: str) -> bool:
    from spark_rapids_tpu.plan.transitions import (
        ColumnarToRowExec, RowToColumnarExec)
    if isinstance(plan, TpuExec):
        if isinstance(plan, RowToColumnarExec):
            return _find_cpu_node(plan.cpu_child, name)
        return any(_find_cpu_node(c, name) for c in plan.children)
    if plan.name() == name:
        return True
    if isinstance(plan, ColumnarToRowExec):
        return _find_cpu_node(plan.tpu_child, name)
    return any(_find_cpu_node(c, name) for c in plan.children)


def _find_tpu_node(plan, name: str) -> bool:
    from spark_rapids_tpu.plan.transitions import (
        ColumnarToRowExec, RowToColumnarExec)
    if isinstance(plan, TpuExec):
        if type(plan).__name__ == name:
            return True
        if isinstance(plan, RowToColumnarExec):
            return _find_tpu_node(plan.cpu_child, name)
        return any(_find_tpu_node(c, name) for c in plan.children)
    if isinstance(plan, ColumnarToRowExec):
        return _find_tpu_node(plan.tpu_child, name)
    return any(_find_tpu_node(c, name) for c in plan.children)


# ---------------------------------------------------------------------------
def accelerate(cpu_plan: N.CpuNode,
               conf: Optional[C.RapidsConf] = None):
    """The full rewrite: returns a TpuExec (fully accelerated), or a
    CpuNode tree with accelerated islands (partial), or the original plan
    (sql disabled).

    A profiled query starts here: the rewrite and the eager source
    upload are recorded as `plan:accelerate` and its children on a
    tracer of this call's own, which is left on the returned plan,
    inert, for the `collect()` that runs it (utils/profile.begin_plan /
    park_plan)."""
    conf = conf or C.get_active_conf()
    if not conf[C.SQL_ENABLED]:
        return cpu_plan
    from spark_rapids_tpu.utils import profile as P
    owner = P.begin_plan(conf)
    plan = None
    try:
        with P.span(P.SPAN_ACCELERATE, cat=P.CAT_PLAN) as sp:
            plan = _accelerate(cpu_plan, conf)
            if sp is not None:
                tpu_nodes, cpu_islands = _plan_census(plan)
                sp.args = {"nodes_in": _count_nodes(cpu_plan),
                           "tpu_nodes_out": tpu_nodes,
                           "cpu_islands": cpu_islands}
        return plan
    finally:
        P.park_plan(owner, plan)


def _count_nodes(cpu_plan: N.CpuNode) -> int:
    return 1 + sum(_count_nodes(c) for c in cpu_plan.children)


def _plan_census(plan) -> tuple[int, int]:
    """(TPU nodes, CPU islands) of an accelerated plan; an island is a
    CPU subtree at the root or under a RowToColumnarExec."""
    from spark_rapids_tpu.plan.transitions import (
        ColumnarToRowExec, RowToColumnarExec)
    tpu = islands = 0
    todo = [(plan, False)]
    while todo:
        node, cpu_above = todo.pop()
        if isinstance(node, TpuExec):
            tpu += 1
            kids = [node.cpu_child] if isinstance(node, RowToColumnarExec) \
                else node.children
        else:
            islands += not cpu_above
            kids = [node.tpu_child] if isinstance(node, ColumnarToRowExec) \
                else node.children
        todo += [(k, not isinstance(node, TpuExec)) for k in kids]
    return tpu, islands


def _accelerate(cpu_plan: N.CpuNode, conf: C.RapidsConf):
    if conf[C.UDF_COMPILER_ENABLED]:
        from spark_rapids_tpu.udf import rewrite_udfs
        cpu_plan = rewrite_udfs(cpu_plan)
    if conf[C.PRUNE_COLUMNS]:
        from spark_rapids_tpu.plan.pruning import prune_columns
        cpu_plan = prune_columns(cpu_plan)
    meta = wrap_plan(cpu_plan, conf)
    meta.tag_for_tpu()
    fix_up_exchange_overhead(meta)
    explain_mode = conf[C.EXPLAIN]
    if explain_mode != "NONE":
        text = meta.explain(all_nodes=(explain_mode == "ALL"))
        if text:
            log.warning("TPU plan overrides:\n%s", text)
    plan = meta.convert_if_needed()
    from spark_rapids_tpu.plan.transitions import (
        _coalesce_cpu_islands, insert_coalesce, optimize_transitions,
        _optimize_tpu)
    from spark_rapids_tpu.plan.fusion import fuse_plan
    from spark_rapids_tpu.exec.base import TargetSize
    if isinstance(plan, TpuExec):
        plan = _optimize_tpu(plan)
        # whole-stage fusion BEFORE coalesce insertion: chains must
        # still be adjacent (a fused stage with filter members keeps
        # coalesce_after, so the re-bucket above it survives)
        plan = fuse_plan(plan, conf)
        plan = insert_coalesce(plan, conf)
    else:
        plan = optimize_transitions(plan)
        plan = fuse_plan(plan, conf)
        _coalesce_cpu_islands(plan, TargetSize(conf[C.BATCH_SIZE_BYTES]),
                              conf[C.MAX_BATCH_ROWS])
    if conf[C.TEST_ENABLED]:
        from spark_rapids_tpu.plan.transitions import assert_is_on_tpu
        allowed = {s for s in
                   str(conf[C.TEST_ALLOWED_NONGPU]).split(",") if s}
        assert_is_on_tpu(plan, allowed)
    ExecutionPlanCapture.last_plan = plan
    ExecutionPlanCapture.last_meta = meta
    # carry the session conf to execution: collect() re-installs it so
    # run-time conf reads agree with plan-time decisions.  Re-accelerating
    # the SAME plan object under another conf re-stamps it (last wins) —
    # the session-global conf model of the reference.
    try:
        plan._session_conf = conf
    except AttributeError:
        pass  # frozen/slots nodes keep their creation conf
    return plan


def collect(plan, conf: Optional[C.RapidsConf] = None) -> "object":
    """Run an accelerated (or partially accelerated) plan to a pandas
    DataFrame — the driver-side collect.  With spark.sql.adaptive.enabled,
    fully-TPU plans are executed stage-at-a-time with runtime re-planning
    (plan/aqe.py).

    Serving-layer duties live here: the plan-fingerprint RESULT CACHE
    (a hit returns the cached frame bit-exactly without touching the
    device) and the per-query scope — one QueryContext covering the
    whole drive (deopt retries, the AQE stage loop, partial CPU plans)
    that carries the session conf snapshot, the CancelToken, the
    profile, and the HBM admission slot."""
    conf = conf or getattr(plan, "_session_conf", None) or \
        C.get_active_conf()
    from spark_rapids_tpu.exec import scheduler as S
    with C.session(conf):
        cache_key = S.result_cache_key(plan, conf)
        if cache_key is not None:
            hit = S.result_cache().get(cache_key)
            if hit is not None:
                return hit
        out = _collect(plan, conf)
        if cache_key is not None and hasattr(out, "memory_usage"):
            S.result_cache().put(cache_key, out,
                                 int(conf[C.RESULT_CACHE_MAX_BYTES]))
        return out


def _collect(plan, conf: C.RapidsConf) -> "object":
    """Adds the deopt-and-retry boundary for PARTIALLY accelerated plans:
    a mid-plan TPU->CPU transition (df_from_batch / serde) may raise
    FastPathInvalid from a deferred fast-path check; the offending fast
    path is disabled and the pure plan re-executes once."""
    from spark_rapids_tpu.exec import scheduler as S
    from spark_rapids_tpu.utils import checks as CK
    scope = S.QueryScope(conf, plan)
    error: Optional[BaseException] = None
    try:
        mark = CK.snapshot()
        try:
            return _collect_inner(plan, conf)
        except CK.FastPathInvalid as e:
            e.recover_all()
            CK.drain_since(mark)
            CK.set_retrying(True)
            try:
                return _collect_inner(plan, conf)
            finally:
                CK.set_retrying(False)
    except BaseException as e:
        error = e
        raise
    finally:
        scope.close(error=error)


def _collect_to_host(plan: TpuExec) -> "object":
    """`plan.collect()` as host rows.  The conversion is the second half
    of the query's `exec:Readback`; the first, the wait for the device
    and the stacked flag read, is `TpuExec.collect`'s."""
    from spark_rapids_tpu.plan.transitions import df_from_batch
    from spark_rapids_tpu.utils import profile as P
    batch = plan.collect()
    with P.span(P.SPAN_READBACK) as sp:
        df = df_from_batch(batch)
        if sp is not None:
            sp.args = {"phase": "convert", "rows": len(df),
                       "bytes": batch.device_size_bytes()}
    return df


def _collect_inner(plan, conf: C.RapidsConf) -> "object":
    if isinstance(plan, TpuExec):
        if conf[C.ADAPTIVE_ENABLED]:
            from spark_rapids_tpu.plan.aqe import (adaptive_execute,
                                                   release_stage_buffers)
            # the AQE drive materializes stages BEFORE the root
            # collect: own the query profile here so prestarted map
            # sides trace too (plan.collect's begin_query then sees an
            # active tracer and leaves ownership alone)
            from spark_rapids_tpu.utils import profile as P
            prof_owner = P.begin_query(conf)
            prof_error = None
            try:
                plan = adaptive_execute(plan, conf)
                ExecutionPlanCapture.last_plan = plan
                try:
                    return _collect_to_host(plan)
                finally:
                    # the captured plan must not pin the query's entire
                    # shuffle output in device memory
                    release_stage_buffers(plan)
            except BaseException as e:
                prof_error = e
                raise
            finally:
                P.end_query(prof_owner, plan, error=prof_error)
        return _collect_to_host(plan)
    return plan.collect()
