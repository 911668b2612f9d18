"""Plan transitions: row<->columnar bridges and coalesce insertion
(reference `GpuTransitionOverrides.scala`: GpuRowToColumnarExec /
GpuColumnarToRowExec / GpuCoalesceBatches placement, redundant-transition
elimination, test-mode assertIsOnTheGpu;
`GpuRowToColumnarExec.scala`/`GpuColumnarToRowExec.scala` converters).

The CPU side trades in pandas DataFrames with nullable dtypes; the TPU side
in ColumnarBatch.  `RowToColumnarExec` uploads (host build -> HBM);
`ColumnarToRowExec` downloads and releases the task's TPU semaphore, the
same leave-the-device point as the reference (GpuColumnarToRowExec.scala:80).
"""
from __future__ import annotations

from typing import Iterator, Optional

import numpy as np
import pandas as pd

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import host_strings
from spark_rapids_tpu.exec.base import (
    CoalesceGoal, LeafExec, TargetSize, TpuExec, max_goal)
from spark_rapids_tpu.exec.coalesce import CoalesceBatchesExec
from spark_rapids_tpu.memory.semaphore import TpuSemaphore
from spark_rapids_tpu.plan.cpu_eval import nullable_dtype
from spark_rapids_tpu.plan.nodes import CpuNode, normalize_df
from spark_rapids_tpu.utils import metrics as M


def host_columns_from_df(df: pd.DataFrame, schema: T.Schema
                         ) -> tuple[dict, dict]:
    """The host half of `batch_from_df`: pandas columns -> numpy storage
    arrays and validity masks, nothing on the device yet.  A STRING
    column stays columnar: the Arrow `large_string` array a pandas `str`
    column holds (a partition's slice keeps it, at an offset), or one
    made of an object column in one pass (`host_strings`; None and NaN
    null).  Only what Arrow refuses (numbers, other objects) becomes an
    object array of Python values, for the per-value encoder."""
    data, validity = {}, {}
    for f in schema.fields:
        s = df[f.name]
        if f.dtype.id == T.TypeId.DATE32 and s.dtype == object:
            # python date objects -> int32 days storage
            s = normalize_df(df[[f.name]], T.Schema((f,)))[f.name]
        mask = s.isna().to_numpy() if hasattr(s, "isna") else None
        if f.dtype.is_string:
            strings = host_strings(
                s.to_numpy() if s.dtype == object else s.array,
                nan_is_null=True)
            data[f.name] = strings if strings is not None else np.array(
                [None if m else v for v, m in zip(s.tolist(), mask)],
                dtype=object)
        else:
            storage = f.dtype.storage_dtype
            if str(s.dtype).startswith(("Int", "Float", "boolean")):
                vals = s.fillna(0).to_numpy(dtype=storage)
            elif s.dtype.kind == "M":
                vals = s.to_numpy().astype("datetime64[us]").astype(np.int64)
                vals = np.where(mask, 0, vals)
            else:
                vals = s.to_numpy().astype(storage, copy=False)
                if mask.any() and vals.dtype.kind == "f":
                    vals = np.where(mask, 0, vals)
            data[f.name] = vals
        validity[f.name] = ~mask
    return data, validity


def batch_from_df(df: pd.DataFrame, schema: T.Schema) -> ColumnarBatch:
    """Host rows -> device batch honoring the schema's storage model
    (GpuRowToColumnarExec converter analog, but columnar-at-once: pandas
    already stores columns contiguously, so we upload per column)."""
    data, validity = host_columns_from_df(df, schema)
    return ColumnarBatch.from_numpy(data, schema, validity)


def series_from_column(field: T.Field, vals, valid) -> pd.Series:
    """One host column -> nullable pandas Series; shared by every
    device-exit strategy so dtype semantics cannot drift between shims."""
    if field.dtype.is_string:
        return pd.Series(list(vals), dtype=object)
    s = pd.Series(vals).astype(nullable_dtype(field.dtype))
    # tpulint: disable=host-sync -- valid is host-resident here: every
    # caller passes the output of to_numpy()/device_get(), which are
    # the accounted readback points
    s[~np.asarray(valid)] = pd.NA
    return s


def df_from_batch(batch: ColumnarBatch) -> pd.DataFrame:
    """Device batch -> host rows with nullable dtypes (storage model
    preserved: DATE32 stays int days, TIMESTAMP_US stays int micros), so
    downstream CPU operators see exactly what cpu_eval expects.

    Prefetches every buffer (async D2H) before converting, so the whole
    batch comes back in one wave instead of one blocking readback per
    buffer."""
    batch = batch.dense()
    # movement ledger: the engine's result sink pulls the full padded
    # device arrays (the collect-boundary readback)
    from spark_rapids_tpu.utils import movement as MV
    if MV.ledger() is not None:
        MV.record(MV.EDGE_READBACK, batch.device_size_bytes(),
                  site="collect.df_from_batch")
    batch.prefetch()
    batch.verify_checks()
    out = {}
    for f, c in zip(batch.schema.fields, batch.columns):
        vals, valid = c.to_numpy(batch.num_rows)
        out[f.name] = series_from_column(f, vals, valid)
    return pd.DataFrame(out)


class HostColumnarToDeviceExec(LeafExec):
    """HOST-COLUMNAR source → device batches (reference
    `HostColumnarToGpu.scala`, 273 LoC: cached/InMemoryTableScan data
    enters the GPU plan without a row pivot).  Column buffers upload via
    `ColumnarBatch.from_arrow`; oversized tables chunk by the batch-row
    cap like the scan path."""

    def __init__(self, cpu_source):
        super().__init__()
        self.cpu_source = cpu_source  # CpuCachedColumnar
        self._schema = cpu_source.output_schema()

    def output_schema(self) -> T.Schema:
        return self._schema

    def output_partition_count(self) -> int:
        return self.cpu_source.output_partition_count()

    def describe(self):
        return (f"HostColumnarToDeviceExec("
                f"{len(self.cpu_source.partitions)} cached partitions)")

    def execute_partitions(self):
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        max_rows = C.get_active_conf()[C.MAX_BATCH_ROWS]

        def convert(table):
            sem = TpuSemaphore.get()
            for off in range(0, max(table.num_rows, 1), max_rows):
                sl = table.slice(off, max_rows)
                if sl.num_rows == 0:
                    continue
                sem.acquire_if_necessary()  # device admission boundary
                with self.metrics.timed(M.TOTAL_TIME):
                    b = ColumnarBatch.from_arrow(sl)
                    self.update_output_metrics(b)
                yield b
        outs = [convert(t) for t in self.cpu_source.partitions]
        return outs or [iter(())]

    def execute_columnar(self):
        for it in self.execute_partitions():
            yield from it


class RowToColumnarExec(LeafExec):
    """Runs a CPU subtree and uploads its partitions to the device
    (reference GpuRowToColumnarExec; leaf from the TPU tree's viewpoint)."""

    def __init__(self, cpu_child: CpuNode):
        super().__init__()
        self.cpu_child = cpu_child
        self._schema = cpu_child.output_schema()

    def output_schema(self) -> T.Schema:
        return self._schema

    def output_partition_count(self) -> int:
        return self.cpu_child.output_partition_count()

    def describe(self):
        return f"RowToColumnarExec\n{self.cpu_child.tree_string(1)}"

    def execute_partitions(self):
        max_rows = C.get_active_conf()[C.MAX_BATCH_ROWS]

        def convert(it):
            for df in it:
                if not len(df):
                    continue
                # chunk BEFORE upload so device batch capacities stay in
                # the bounded bucketed set (one compile serves them all)
                for lo in range(0, len(df), max_rows):
                    chunk = df.iloc[lo:lo + max_rows]
                    with self.metrics.timed(M.TOTAL_TIME):
                        TpuSemaphore.get().acquire_if_necessary()
                        b = batch_from_df(chunk, self._schema)
                        self.update_output_metrics(b)
                    yield b
        return [convert(it) for it in self.cpu_child.execute()]

    def execute_columnar(self):
        for it in self.execute_partitions():
            yield from it


class ColumnarToRowExec(CpuNode):
    """Runs a TPU subtree and downloads batches to pandas rows, releasing
    the semaphore at the device-exit boundary (reference
    GpuColumnarToRowExec.scala:80)."""

    def __init__(self, tpu_child: TpuExec):
        super().__init__()
        self.tpu_child = tpu_child
        self._schema = tpu_child.output_schema()

    def output_schema(self) -> T.Schema:
        return self._schema

    def output_partition_count(self) -> int:
        return self.tpu_child.output_partition_count()

    def describe(self):
        return f"{self.name()}\n{self.tpu_child.tree_string(1)}"

    def execute(self):
        def convert(it):
            for batch in it:
                df = df_from_batch(batch)
                TpuSemaphore.get().release_if_necessary()
                yield df
        return [convert(it) for it in self.tpu_child.execute_partitions()]


class AcceleratedColumnarToRowExec(ColumnarToRowExec):
    """Spark 3.1.0's accelerated device-exit transition (reference
    `SparkShims.getGpuColumnarToRowTransition`, spark310 shim): all
    columns of a batch leave the device in ONE packed transfer
    (`jax.device_get` of the whole pytree) instead of per-column syncs."""

    def execute(self):
        import jax

        def convert(it):
            from spark_rapids_tpu.utils import checks as CK
            for batch in it:
                n = batch.num_rows
                pairs = [(c.data, c.validity) for c in batch.columns
                         if not c.dtype.is_string]
                CK.note_host_sync(
                    "transition.device_get",
                    nbytes=sum(int(d.nbytes) + int(v.nbytes)
                               for d, v in pairs))
                host = list(jax.device_get(pairs))
                out = {}
                for f, c in zip(batch.schema.fields, batch.columns):
                    if f.dtype.is_string:
                        vals, valid = c.to_numpy(n)
                    else:
                        data, validity = host.pop(0)
                        vals, valid = data[:n], validity[:n]
                    out[f.name] = series_from_column(f, vals, valid)
                TpuSemaphore.get().release_if_necessary()
                yield pd.DataFrame(out)
        return [convert(it) for it in self.tpu_child.execute_partitions()]


class BringBackToHost(CpuNode):
    """Terminal marker above the last columnar node (reference
    GpuBringBackToHost): collect point for driver-side results."""

    def __init__(self, child: CpuNode):
        super().__init__(child)

    def output_schema(self):
        return self.child.output_schema()

    def execute(self):
        return self.child.execute()


# ---------------------------------------------------------------------------
def insert_coalesce(plan: TpuExec, conf: C.RapidsConf) -> TpuExec:
    """Insert CoalesceBatchesExec per each node's childrenCoalesceGoal and
    after batch-shrinking nodes (reference
    GpuTransitionOverrides.insertCoalesce :114-199)."""
    target = TargetSize(conf[C.BATCH_SIZE_BYTES])
    _insert_coalesce_walk(plan, target, conf[C.MAX_BATCH_ROWS])
    return plan


def _insert_coalesce_walk(node: TpuExec, target: TargetSize,
                          max_rows: Optional[int] = None) -> None:
    if isinstance(node, RowToColumnarExec):
        # descend through the CPU island: TPU subtrees inside it need
        # coalesce too
        _coalesce_cpu_islands(node.cpu_child, target, max_rows)
        return
    goals = node.children_coalesce_goal()
    for i, child in enumerate(list(node.children)):
        goal: Optional[CoalesceGoal] = goals[i] if i < len(goals) else None
        if getattr(child, "coalesce_after", False):
            goal = max_goal(goal, target)
        if goal is not None and not isinstance(child, CoalesceBatchesExec):
            node._children[i] = CoalesceBatchesExec(goal, child, max_rows)
        _insert_coalesce_walk(child, target, max_rows)


def _coalesce_cpu_islands(node: CpuNode, target: TargetSize,
                          max_rows: Optional[int] = None) -> None:
    if isinstance(node, ColumnarToRowExec):
        _insert_coalesce_walk(node.tpu_child, target, max_rows)
        return
    for c in node.children:
        _coalesce_cpu_islands(c, target, max_rows)


def optimize_transitions(node: CpuNode) -> CpuNode:
    """Remove C2R(R2C(x)) / R2C(C2R(x)) pairs introduced at fallback
    islands (reference optimizeGpuPlanTransitions)."""
    if isinstance(node, ColumnarToRowExec):
        node.tpu_child = _optimize_tpu(node.tpu_child)
        if isinstance(node.tpu_child, RowToColumnarExec):
            return optimize_transitions(node.tpu_child.cpu_child)
        return node
    node.children = [optimize_transitions(c) for c in node.children]
    return node


def _optimize_tpu(node: TpuExec) -> TpuExec:
    if isinstance(node, RowToColumnarExec):
        node.cpu_child = optimize_transitions(node.cpu_child)
        if isinstance(node.cpu_child, ColumnarToRowExec):
            return _optimize_tpu(node.cpu_child.tpu_child)
        return node
    node._children = [_optimize_tpu(c) for c in node.children]
    return node


def assert_is_on_tpu(plan, allowed: set[str] = frozenset()) -> None:
    """Test hook (reference assertIsOnTheGpu, conf
    spark.rapids.sql.test.enabled): every CPU node must be in `allowed`."""
    from spark_rapids_tpu.plan.nodes import CpuSource

    def walk_cpu(node: CpuNode):
        if isinstance(node, ColumnarToRowExec):
            walk_tpu(node.tpu_child)
            return
        if not isinstance(node, (BringBackToHost, CpuSource)) and \
                node.name() not in allowed:
            raise AssertionError(
                f"plan node {node.name()} did not run on the TPU:\n"
                f"{node.tree_string()}")
        for c in node.children:
            walk_cpu(c)

    def walk_tpu(node: TpuExec):
        if isinstance(node, RowToColumnarExec):
            walk_cpu(node.cpu_child)
            return
        for c in node.children:
            walk_tpu(c)

    if isinstance(plan, TpuExec):
        walk_tpu(plan)
    else:
        walk_cpu(plan)
