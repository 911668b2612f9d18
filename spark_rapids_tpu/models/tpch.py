"""TPC-H workload definitions (reference
`integration_tests/src/main/scala/.../tpch/TpchLikeSpark.scala`).

Queries are built as physical plans over the engine; `build_q1_kernel`
additionally exposes Q1's compute as ONE pure jittable function — the
"flagship forward step" used by __graft_entry__.

Q1 (pricing summary report):
  select returnflag, linestatus, sum(qty), sum(extprice),
         sum(extprice*(1-disc)), sum(extprice*(1-disc)*(1+tax)),
         avg(qty), avg(extprice), avg(disc), count(*)
  from lineitem where shipdate <= date '1998-09-02'
  group by returnflag, linestatus
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.base import make_eval_context
from spark_rapids_tpu.exprs.base import col, lit
from spark_rapids_tpu.exprs.aggregates import (
    AggContext, Average, Count, CountStar, Sum)
from spark_rapids_tpu.ops.sort_encode import (
    multi_key_argsort, segment_boundaries)

LINEITEM_SCHEMA = T.Schema.of(
    ("l_returnflag", T.INT32),      # dictionary-encoded flag (A/N/R -> 0/1/2)
    ("l_linestatus", T.INT32),      # O/F -> 0/1
    ("l_quantity", T.FLOAT32),
    ("l_extendedprice", T.FLOAT32),
    ("l_discount", T.FLOAT32),
    ("l_tax", T.FLOAT32),
    ("l_shipdate", T.DATE32),
)

Q1_CUTOFF_DAYS = 10471  # 1998-09-02 as days since epoch


def gen_lineitem(rng: np.random.Generator, rows: int) -> ColumnarBatch:
    """Synthetic lineitem in TPC-H value ranges (dbgen-shaped, not dbgen
    bit-exact — the engine is being measured, not the generator)."""
    base = {
        "l_returnflag": rng.integers(0, 3, rows).astype(np.int32),
        "l_linestatus": rng.integers(0, 2, rows).astype(np.int32),
        "l_quantity": rng.integers(1, 51, rows).astype(np.float32),
        "l_extendedprice": np.round(
            rng.uniform(900.0, 105000.0, rows), 2).astype(np.float32),
        "l_discount": np.round(
            rng.uniform(0.0, 0.10, rows), 2).astype(np.float32),
        "l_tax": np.round(
            rng.uniform(0.0, 0.08, rows), 2).astype(np.float32),
        "l_shipdate": rng.integers(8400, 10600, rows).astype(np.int32),
    }
    return ColumnarBatch.from_numpy(base, LINEITEM_SCHEMA)


def q1_plan(source):
    """Q1 as a physical plan (exec pipeline)."""
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.exec.basic import FilterExec, ProjectExec
    from spark_rapids_tpu.exec.sort import SortExec, asc
    filtered = FilterExec(
        col("l_shipdate") <= lit(Q1_CUTOFF_DAYS, T.DATE32), source)
    projected = ProjectExec([
        col("l_returnflag"), col("l_linestatus"), col("l_quantity"),
        col("l_extendedprice"), col("l_discount"),
        (col("l_extendedprice") * (lit(1.0) - col("l_discount"))
         ).alias("disc_price"),
        (col("l_extendedprice") * (lit(1.0) - col("l_discount"))
         * (lit(1.0) + col("l_tax"))).alias("charge"),
    ], filtered)
    agg = HashAggregateExec(
        [col("l_returnflag"), col("l_linestatus")],
        [Sum(col("l_quantity")).alias("sum_qty"),
         Sum(col("l_extendedprice")).alias("sum_base_price"),
         Sum(col("disc_price")).alias("sum_disc_price"),
         Sum(col("charge")).alias("sum_charge"),
         Average(col("l_quantity")).alias("avg_qty"),
         Average(col("l_extendedprice")).alias("avg_price"),
         Average(col("l_discount")).alias("avg_disc"),
         CountStar().alias("count_order")],
        projected)
    return SortExec([asc(col("l_returnflag")), asc(col("l_linestatus"))],
                    agg)


def build_q1_fused_kernel(capacity: int, batch_rows: int):
    """STACKED Q1 step: one dispatch aggregates capacity // batch_rows
    batches laid back to back (num_rows becomes a per-batch vector) —
    the device-side batch loop that amortizes per-dispatch runtime
    overhead.  Pallas single-HBM-pass kernel by default
    (spark.rapids.tpu.pallas.q1Fused.enabled, measured 3x XLA); falls
    back to vmapping the XLA step over the batch axis."""
    import jax
    from spark_rapids_tpu import config as C
    b = capacity // batch_rows
    pallas_ok = (b == 1) or (batch_rows % 1024 == 0)
    if C.get_active_conf()[C.PALLAS_Q1_FUSED_ENABLED] and pallas_ok:
        from spark_rapids_tpu.ops.pallas_kernels import (_on_tpu,
                                                         q1_fused_pallas)
        interp = not _on_tpu()

        def step(flag, status, qty, extprice, disc, tax, shipdate,
                 nums):
            return q1_fused_pallas(
                flag, status, qty, extprice, disc, tax, shipdate, nums,
                capacity=capacity, cutoff=Q1_CUTOFF_DAYS,
                batch_rows=batch_rows, interpret=interp)

        return step
    base = build_q1_kernel(batch_rows)

    @jax.jit
    def step(flag, status, qty, extprice, disc, tax, shipdate, nums):
        cols = [x.reshape(b, batch_rows)
                for x in (flag, status, qty, extprice, disc, tax,
                          shipdate)]
        outs = jax.vmap(base)(*cols, nums)
        # per-batch (8,) group rows -> combined (8, 6) table
        import jax.numpy as jnp
        return jnp.stack([outs[2 + j].sum(axis=0) for j in range(5)] +
                         [outs[7].sum(axis=0).astype(jnp.float64)],
                         axis=1)

    return step


def q1_reference_pandas(df):
    """Golden CPU implementation for parity checks."""
    f = df[df["l_shipdate"] <= Q1_CUTOFF_DAYS].copy()
    f["disc_price"] = f["l_extendedprice"] * (1 - f["l_discount"])
    f["charge"] = f["disc_price"] * (1 + f["l_tax"])
    out = f.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"),
    ).reset_index().sort_values(["l_returnflag", "l_linestatus"])
    return out


def build_q1_kernel(capacity: int):
    """Q1 compute as ONE pure jittable function over column arrays:
       fn(qty, extprice, disc, tax, flag, status, shipdate, num_rows)
         -> (flag6, status6, sums..., counts)
    Output is a fixed 8-slot group table (3 flags x 2 statuses padded to
    8), fully static shapes — the whole query is a single fused XLA
    computation: the flagship single-chip forward step.

    With spark.rapids.tpu.pallas.q1.enabled the explicit Pallas kernel
    (ops/pallas_kernels.py) is returned instead — same contract."""
    from spark_rapids_tpu import config as C
    if C.get_active_conf()[C.PALLAS_Q1_ENABLED]:
        from spark_rapids_tpu.ops.pallas_kernels import (
            build_q1_kernel_pallas)
        return build_q1_kernel_pallas(capacity, Q1_CUTOFF_DAYS)
    cap = capacity

    def q1_step(flag, status, qty, extprice, disc, tax, shipdate,
                num_rows):
        row_mask = jnp.arange(cap) < num_rows
        keep = row_mask & (shipdate <= Q1_CUTOFF_DAYS)
        disc_price = extprice * (1.0 - disc)
        charge = disc_price * (1.0 + tax)
        # group id = flag * 2 + status, 6 groups (static!)
        gid = jnp.where(keep, flag * 2 + status, 7)
        # grouped reduction as one-hot matmuls on the MXU: scatter
        # (segment_sum) serializes on TPU, but (rows x 6)^T @ (rows x 8)
        # one-hot is a systolic-array pass — the elementwise prologue
        # fuses into the matmul's operand reads.  Chunked to 64K rows
        # with an f64 combine: a single f32 accumulation over millions of
        # rows loses ~1e-4 relative (HIGHEST only fixes operand
        # rounding, not the f32 accumulator).
        onehot = (gid[:, None] == jnp.arange(8)[None, :]).astype(
            jnp.float32)
        # jnp.where, not multiply-by-mask: NaN in a filtered-out row
        # must not poison the sums (NaN * 0 == NaN)
        vals = jnp.where(
            keep[:, None],
            jnp.stack([qty, extprice, disc_price, charge, disc,
                       jnp.ones_like(qty)], axis=1),
            jnp.float32(0))
        chunk = min(cap, 65536)
        table = jnp.einsum(
            "cbm,cbg->cmg", vals.reshape(-1, chunk, 6),
            onehot.reshape(-1, chunk, 8),
            precision=jax.lax.Precision.HIGHEST,
        ).astype(jnp.float64).sum(axis=0)
        g = jnp.arange(8)
        cnt = table[5].astype(jnp.int32)
        return (g // 2, g % 2, table[0], table[1], table[2], table[3],
                table[4], cnt)

    return q1_step
