"""Expand and Generate operators (reference `GpuExpandExec.scala` 202 LoC,
`GpuGenerateExec.scala` 194 LoC).

ExpandExec: each input row emits one output row per projection list —
the grouping-sets/rollup/cube building block.  On TPU the expansion is a
static-fan-out gather: output capacity = capacity * num_projections, and
every projection's expressions evaluate over the same input batch (one
fused kernel).

GenerateExec: explode over an inline array of expressions
(`explode(array(e1..eN))`, the pattern the reference accelerates at this
snapshot — there is no first-class array column type in the v0 matrix).
"""
from __future__ import annotations

from typing import Iterator, Sequence

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import ColumnVector
from spark_rapids_tpu.exec.base import (
    TpuExec, UnaryExecBase, batch_signature, make_eval_context,
    named_jit)
from spark_rapids_tpu.exprs.base import Expression, output_name
from spark_rapids_tpu.utils import metrics as M


class ExpandExec(UnaryExecBase):
    def __init__(self, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str], child: TpuExec):
        super().__init__(child)
        child_schema = child.output_schema()
        self.projections = [list(p) for p in projections]
        self._bound = [[e.bind(child_schema) for e in p]
                       for p in self.projections]
        dts = [b.data_type(child_schema) for b in self._bound[0]]
        for p in self._bound[1:]:
            for i, b in enumerate(p):
                dt = b.data_type(child_schema)
                if dt != dts[i]:
                    dts[i] = T.common_type(dts[i], dt)
        self._schema = T.Schema(tuple(
            T.Field(n, dt) for n, dt in zip(names, dts)))

    @property
    def coalesce_after(self) -> bool:
        return True

    def output_schema(self):
        return self._schema

    def describe(self):
        return f"ExpandExec({len(self.projections)} projections)"

    def cache_scope(self):
        from spark_rapids_tpu.exprs.base import fingerprint
        return (fingerprint(self._bound), fingerprint(self._schema))

    def _kernel(self, batch: ColumnarBatch):
        key = ("expand", batch_signature(batch))

        def build():
            cap = batch.capacity
            nproj = len(self._bound)
            out_cap = cap * nproj

            @named_jit("expand")
            def kernel(columns, num_rows):
                ctx = make_eval_context(columns, cap, num_rows)
                # evaluate every projection, then interleave rows:
                # output row r*nproj + p = projection p of input row r
                per_proj = []
                for p in self._bound:
                    cols = []
                    for e, f in zip(p, self._schema.fields):
                        v = e.eval(ctx)
                        from spark_rapids_tpu.exprs.base import promote
                        if not f.dtype.is_string and v.dtype != f.dtype:
                            v = promote(v, f.dtype)
                        cols.append(v)
                    per_proj.append(cols)
                k = jnp.arange(out_cap)
                src_row = k // nproj
                src_proj = k % nproj
                valid = src_row < num_rows
                out_cols = []
                for ci, f in enumerate(self._schema.fields):
                    if f.dtype.is_string:
                        from spark_rapids_tpu.columnar.vector import \
                            _pad_chars
                        cc = max(per_proj[p][ci].char_cap
                                 for p in range(nproj))
                        vs = [_pad_chars(per_proj[p][ci], cc)
                              for p in range(nproj)]
                        data = jnp.stack([v.data for v in vs])
                        vald = jnp.stack([v.validity for v in vs])
                        lens = jnp.stack([v.lengths for v in vs])
                        d = data[src_proj, jnp.where(valid, src_row, 0)]
                        va = vald[src_proj,
                                  jnp.where(valid, src_row, 0)] & valid
                        ln = lens[src_proj, jnp.where(valid, src_row, 0)]
                        out_cols.append(ColumnVector(
                            f.dtype, d, va, jnp.where(valid, ln, 0)))
                    else:
                        data = jnp.stack(
                            [per_proj[p][ci].data for p in range(nproj)])
                        vald = jnp.stack(
                            [per_proj[p][ci].validity
                             for p in range(nproj)])
                        d = data[src_proj, jnp.where(valid, src_row, 0)]
                        va = vald[src_proj,
                                  jnp.where(valid, src_row, 0)] & valid
                        out_cols.append(ColumnVector(f.dtype, d, va))
                return out_cols

            return kernel

        return self.kernels.get_or_build(
            key, build, meta=self.kp_meta("expand"))

    def process_partition(self, batches) -> Iterator[ColumnarBatch]:
        nproj = len(self._bound)
        for batch in batches:
            batch = batch.dense()
            with self.metrics.timed(M.TOTAL_TIME):
                kern = self._kernel(batch)
                cols = kern(batch.columns, batch.num_rows_i32)
                rows = (batch.num_rows * nproj if batch.num_rows_known
                        else batch.num_rows_i32 * nproj)
                out = ColumnarBatch(self._schema, list(cols),
                                    rows, batch.checks)
                self.update_output_metrics(out)
            yield out


class GenerateExec(UnaryExecBase):
    """explode(array(e1..eN)) [+ posexplode]: each row emits N rows with
    (pos?, value); `outer=True` emits one null row for empty arrays (not
    representable here since N is static and > 0)."""

    def __init__(self, element_exprs: Sequence[Expression],
                 child: TpuExec, include_pos: bool = False,
                 value_name: str = "col", retained: Sequence[str] = None):
        super().__init__(child)
        child_schema = child.output_schema()
        self.include_pos = include_pos
        self._bound = [e.bind(child_schema) for e in element_exprs]
        dt = self._bound[0].data_type(child_schema)
        for b in self._bound[1:]:
            d2 = b.data_type(child_schema)
            if d2 != dt:
                dt = T.common_type(dt, d2)
        self.retained = list(retained) if retained is not None else \
            list(child_schema.names)
        fields = [child_schema.field(n) for n in self.retained]
        if include_pos:
            fields.append(T.Field("pos", T.INT32))
        fields.append(T.Field(value_name, dt))
        self._schema = T.Schema(tuple(fields))
        # as an n-projection expand: projection p = retained + [p, e_p]
        from spark_rapids_tpu.exprs.base import AttributeReference, Literal
        projections = []
        for p, e in enumerate(element_exprs):
            proj = [AttributeReference(n) for n in self.retained]
            if include_pos:
                proj.append(Literal(p, T.INT32))
            proj.append(e)
            projections.append(proj)
        self._expand = ExpandExec(projections,
                                  [f.name for f in fields], child)

    @property
    def coalesce_after(self) -> bool:
        return True

    def output_schema(self):
        return self._schema

    def describe(self):
        return (f"GenerateExec(explode[{len(self._bound)}], "
                f"pos={self.include_pos})")

    def process_partition(self, batches):
        return self._expand.process_partition(batches)
