"""Basic physical operators: Project, Filter, Range, Union, LocalSource
(reference `basicPhysicalOperators.scala:35-177`, `limit.scala`).

Project fuses its whole expression list into ONE jitted kernel per batch
bucket — XLA fuses the expression DAG into a single pass over HBM, which is
the TPU answer to cuDF's per-expression kernel launches.

Filter computes a stable compaction inside the kernel (mask -> packed
gather indices via `jnp.nonzero(..., size=capacity)`), returning the new
row count as a device scalar; only that scalar syncs to host.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import ColumnVector, bucket_capacity
from spark_rapids_tpu.exec.base import (
    LeafExec, TpuExec, UnaryExecBase, batch_signature,
    bind_exprs, make_eval_context, named_jit)
from spark_rapids_tpu.exprs.base import Expression, output_name
from spark_rapids_tpu.utils import metrics as M


def _register_ansi(flags, labels) -> tuple:
    """Register ANSI-mode expression checks (flags returned by the
    kernel, labels captured at trace time) as FATAL deferred checks."""
    if not flags:
        return ()
    from spark_rapids_tpu.utils import checks as CK
    out = []
    for i, flag in enumerate(flags):
        label = labels[i] if i < len(labels) else "ANSI expression check"
        out.append(CK.register(CK.BatchCheck(
            flag, label,
            error=lambda label=label: ArithmeticError(
                f"{label} (spark.sql.ansi.enabled semantics)"))))
    return tuple(out)


class ProjectExec(UnaryExecBase):
    """Reference GpuProjectExec."""

    def __init__(self, exprs: Sequence[Expression], child: TpuExec):
        super().__init__(child)
        self.exprs = list(exprs)
        child_schema = child.output_schema()
        self._bound = bind_exprs(self.exprs, child_schema)
        self._schema = T.Schema(tuple(
            T.Field(output_name(e, i), b.data_type(child_schema))
            for i, (e, b) in enumerate(zip(self.exprs, self._bound))))

    def output_schema(self) -> T.Schema:
        return self._schema

    def cache_scope(self):
        from spark_rapids_tpu.exprs.base import fingerprint
        return (fingerprint(self._bound),)

    def describe(self):
        return f"ProjectExec({', '.join(map(repr, self.exprs))})"

    def _kernel(self, batch: ColumnarBatch):
        key = ("project", batch_signature(batch))

        def build():
            bound = self._bound
            cap = batch.capacity

            labels: list = []

            @named_jit("project")
            def kernel(columns, num_rows, mask=None):
                ctx = make_eval_context(columns, cap, num_rows, mask)
                out = [e.eval(ctx) for e in bound]
                # labels are static per trace; flags are traced outputs
                labels.clear()
                labels.extend(l for l, _ in ctx.pending_checks)
                return out, tuple(f for _, f in ctx.pending_checks)

            kernel._ansi_labels = labels
            return kernel

        return self.kernels.get_or_build(key, build,
                                         meta=self.kp_meta("project"))

    def process_partition(self, batches) -> Iterator[ColumnarBatch]:
        for batch in batches:
            with self.metrics.timed(M.TOTAL_TIME):
                kernel = self._kernel(batch)
                if batch.sparse is not None:
                    out_cols, pend = kernel(batch.columns,
                                            batch.num_rows_i32,
                                            batch.sparse)
                else:
                    out_cols, pend = kernel(batch.columns,
                                            batch.num_rows_i32)
                checks = batch.checks + _register_ansi(
                    pend, kernel._ansi_labels)
                out = ColumnarBatch(self._schema, list(out_cols),
                                    batch._rows, checks,
                                    batch.sparse)
                self.update_output_metrics(out)
            yield out


class FilterExec(UnaryExecBase):
    """Reference GpuFilterExec; sets coalesce_after since filtering shrinks
    batches (GpuExec.coalesceAfter)."""

    def __init__(self, condition: Expression, child: TpuExec):
        super().__init__(child)
        self.condition = condition
        self._bound = condition.bind(child.output_schema())
        self._schema = child.output_schema()

    @property
    def coalesce_after(self) -> bool:
        return True

    def output_schema(self) -> T.Schema:
        return self._schema

    def cache_scope(self):
        from spark_rapids_tpu.exprs.base import fingerprint
        return (fingerprint(self._bound),)

    def describe(self):
        return f"FilterExec({self.condition!r})"

    def _kernel(self, batch: ColumnarBatch):
        key = ("filter", batch_signature(batch))

        def build():
            bound = self._bound
            cap = batch.capacity

            labels: list = []

            @named_jit("filter")
            def kernel(columns, num_rows, mask=None):
                ctx = make_eval_context(columns, cap, num_rows, mask)
                pred = bound.eval(ctx)
                keep = pred.validity & pred.data.astype(bool) & ctx.row_mask
                labels.clear()
                labels.extend(l for l, _ in ctx.pending_checks)
                return (keep, keep.sum().astype(jnp.int32),
                        tuple(f for _, f in ctx.pending_checks))

            kernel._ansi_labels = labels
            return kernel

        return self.kernels.get_or_build(key, build,
                                         meta=self.kp_meta("filter"))

    def process_partition(self, batches) -> Iterator[ColumnarBatch]:
        for batch in batches:
            with self.metrics.timed(M.TOTAL_TIME):
                kernel = self._kernel(batch)
                if batch.sparse is not None:
                    keep, count, pend = kernel(batch.columns,
                                               batch.num_rows_i32,
                                               batch.sparse)
                else:
                    keep, count, pend = kernel(batch.columns,
                                               batch.num_rows_i32)
                # DEFERRED SELECTION: no compaction here — the kept rows
                # ride as a sparse mask; sparse-aware consumers fold it
                # into their row masking, everyone else compacts lazily
                checks = batch.checks + _register_ansi(
                    pend, kernel._ansi_labels)
                out = ColumnarBatch(self._schema, batch.columns, count,
                                    checks, sparse=keep)
                self.update_output_metrics(out)
            yield out


class LocalBatchSource(LeafExec):
    """Test/source exec over in-memory batches (one partition per list)."""

    def __init__(self, partitions: list[list[ColumnarBatch]],
                 schema: Optional[T.Schema] = None):
        super().__init__()
        self.partitions = partitions
        first = next((b for p in partitions for b in p), None)
        self._schema = schema or (first.schema if first else T.Schema(()))

    def output_schema(self) -> T.Schema:
        return self._schema

    def output_partition_count(self) -> int:
        return max(1, len(self.partitions))

    def execute_columnar(self):
        for part in self.partitions:
            yield from part

    def execute_partitions(self):
        return [iter(p) for p in self.partitions]

    @staticmethod
    def from_pandas(df, num_partitions: int = 1) -> "LocalBatchSource":
        n = len(df)
        if num_partitions <= 1 or n == 0:
            return LocalBatchSource([[ColumnarBatch.from_pandas(df)]])
        bounds = np.linspace(0, n, num_partitions + 1).astype(int)
        parts = []
        for i in range(num_partitions):
            chunk = df.iloc[bounds[i]: bounds[i + 1]].reset_index(drop=True)
            parts.append([ColumnarBatch.from_pandas(chunk)]
                         if len(chunk) else [])
        return LocalBatchSource(parts)


class RangeExec(LeafExec):
    """Reference GpuRangeExec: generate [start, end) step in target-size
    chunks, on device via iota."""

    def __init__(self, start: int, end: int, step: int = 1,
                 num_partitions: int = 1, target_rows: int = 1 << 20,
                 name: str = "id"):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.num_partitions = max(1, num_partitions)
        self.target_rows = target_rows
        self._schema = T.Schema.of((name, T.INT64, False))

    def output_partition_count(self) -> int:
        return self.num_partitions

    def output_schema(self) -> T.Schema:
        return self._schema

    def _partition_bounds(self):
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self.num_partitions)
        for p in range(self.num_partitions):
            lo = min(p * per, total)
            hi = min((p + 1) * per, total)
            yield lo, hi

    def _gen(self, lo: int, hi: int) -> Iterator[ColumnarBatch]:
        i = lo
        while i < hi:
            n = min(self.target_rows, hi - i)
            cap = bucket_capacity(n)
            data = (self.start
                    + (jnp.arange(cap, dtype=jnp.int64) + i) * self.step)
            validity = jnp.arange(cap) < n
            col = ColumnVector(T.INT64, data, validity)
            batch = ColumnarBatch(self._schema, [col], n)
            self.update_output_metrics(batch)
            yield batch
            i += n

    def execute_columnar(self):
        for lo, hi in self._partition_bounds():
            yield from self._gen(lo, hi)

    def execute_partitions(self):
        return [self._gen(lo, hi) for lo, hi in self._partition_bounds()]


class UnionExec(TpuExec):
    """Reference GpuUnionExec: concatenation of children's partitions."""

    def __init__(self, *children: TpuExec):
        super().__init__(*children)
        self._schema = children[0].output_schema()

    def output_schema(self):
        return self._schema

    def execute_columnar(self):
        for c in self.children:
            for b in c.execute_columnar():
                out = ColumnarBatch(self._schema, b.columns, b._rows,
                                    b.checks, b.sparse)
                self.update_output_metrics(out)
                yield out

    def output_partition_count(self) -> int:
        return sum(c.output_partition_count() for c in self.children)

    def execute_partitions(self):
        parts = []
        for c in self.children:
            parts.extend(c.execute_partitions())
        return parts


class CoalescePartitionsExec(UnaryExecBase):
    """Reference GpuCoalesceExec (partition coalesce, not batch coalesce)."""

    def __init__(self, num_partitions: int, child: TpuExec):
        super().__init__(child)
        self.num_partitions = max(1, num_partitions)

    def output_partition_count(self) -> int:
        return min(self.num_partitions, self.child.output_partition_count())

    def output_schema(self):
        return self.child.output_schema()

    def execute_partitions(self):
        kids = self.child.execute_partitions()
        groups: list[list] = [[] for _ in range(
            min(self.num_partitions, max(1, len(kids))))]
        for i, it in enumerate(kids):
            groups[i % len(groups)].append(it)

        def chain(its):
            for it in its:
                yield from it
        return [chain(g) for g in groups]

    def execute_columnar(self):
        for it in self.execute_partitions():
            yield from it
