"""Expand/Generate execs + misc expressions."""
import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.basic import LocalBatchSource, ProjectExec
from spark_rapids_tpu.exec.expand import ExpandExec, GenerateExec
from spark_rapids_tpu.exprs import misc as MX
from spark_rapids_tpu.exprs.base import col, lit


def test_expand_grouping_sets():
    df = pd.DataFrame({"a": np.array([1, 2], np.int64),
                       "b": np.array([10, 20], np.int64)})
    # grouping sets ((a), (b)) style expand
    plan = ExpandExec(
        [[col("a"), lit(None, T.INT64), col("b")],
         [lit(None, T.INT64), col("b"), col("b")]],
        ["a", "b", "v"], LocalBatchSource.from_pandas(df))
    out = plan.collect()
    assert out.num_rows == 4
    assert out.column("a").to_pylist(4) == [1, None, 2, None]
    assert out.column("b").to_pylist(4) == [None, 10, None, 20]
    assert out.column("v").to_pylist(4) == [10, 10, 20, 20]


def test_generate_explode():
    df = pd.DataFrame({"k": np.array([7, 8], np.int64),
                       "x": np.array([1, 2], np.int64),
                       "y": np.array([100, 200], np.int64)})
    plan = GenerateExec([col("x"), col("y")],
                        LocalBatchSource.from_pandas(df),
                        include_pos=True, retained=["k"])
    out = plan.collect()
    assert out.num_rows == 4
    assert out.column("k").to_pylist(4) == [7, 7, 8, 8]
    assert out.column("pos").to_pylist(4) == [0, 1, 0, 1]
    assert out.column("col").to_pylist(4) == [1, 100, 2, 200]


def test_monotonic_id_and_partition_id():
    df = pd.DataFrame({"x": np.arange(5, dtype=np.int64)})
    MX.set_task_context(MX.TaskContextInfo(partition_id=3, row_offset=10))
    out = ProjectExec([MX.MonotonicallyIncreasingID().alias("id"),
                       MX.SparkPartitionID().alias("pid")],
                      LocalBatchSource.from_pandas(df)).collect()
    base = (3 << 33) + 10
    assert out.column("id").to_pylist(5) == [base + i for i in range(5)]
    assert out.column("pid").to_pylist(5) == [3] * 5
    MX.set_task_context(MX.TaskContextInfo())


def test_rand_deterministic():
    df = pd.DataFrame({"x": np.arange(100, dtype=np.int64)})
    src = LocalBatchSource.from_pandas(df)
    out1 = ProjectExec([MX.Rand(42).alias("r")], src).collect()
    out2 = ProjectExec([MX.Rand(42).alias("r")], src).collect()
    v1 = out1.column("r").to_pylist(100)
    v2 = out2.column("r").to_pylist(100)
    assert v1 == v2
    assert all(0.0 <= v < 1.0 for v in v1)
    assert len(set(v1)) > 90  # actually random


def test_normalize_nan_zero():
    b = ColumnarBatch.from_numpy({"x": np.array([-0.0, 0.0, np.nan, 1.5])})
    out = ProjectExec([MX.NormalizeNaNAndZero(col("x")).alias("n")],
                      LocalBatchSource([[b]])).collect()
    import math
    got = out.column("n").to_pylist(4)
    assert math.copysign(1, got[0]) == 1.0  # -0.0 -> +0.0
    assert got[1] == 0.0 and math.isnan(got[2]) and got[3] == 1.5


# -- planner-level Expand/Generate ------------------------------------------
def test_cpu_expand_rollup_through_accelerate():
    """Rollup-shaped expand (grouping sets) planned via accelerate():
    projections (a,b,gid=0),(a,null,1),(null,null,3) then aggregate —
    the exact shape Spark lowers ROLLUP(a,b) to."""
    import pandas as pd
    from parity import compare_frames
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.aggregates import Sum
    from spark_rapids_tpu.exprs.base import col, Literal
    from spark_rapids_tpu.plan import (
        CpuAggregate, CpuExpand, CpuSource, ExecutionPlanCapture,
        accelerate, collect)
    df = pd.DataFrame({
        "a": np.array([1, 1, 2, 2, 2], np.int64),
        "b": np.array([10, 20, 10, 10, 30], np.int64),
        "v": np.array([1.0, 2.0, 3.0, 4.0, 5.0]),
    })
    src = CpuSource.from_pandas(df, num_partitions=2)
    expand = CpuExpand(
        [[col("a"), col("b"), Literal(0, T.INT32), col("v")],
         [col("a"), Literal(None, T.INT64), Literal(1, T.INT32), col("v")],
         [Literal(None, T.INT64), Literal(None, T.INT64),
          Literal(3, T.INT32), col("v")]],
        ["a", "b", "gid", "v"], src)
    plan = CpuAggregate([col("a"), col("b"), col("gid")],
                        [Sum(col("v")).alias("sv")], expand)
    expected = plan.collect()
    got = collect(accelerate(plan, C.RapidsConf()))
    assert len(expected) == 7  # 4 (a,b) groups + 2 a groups + 1 total
    ExecutionPlanCapture.assert_contains_tpu("ExpandExec")
    compare_frames(expected, got, "rollup")


def test_cpu_generate_posexplode_through_accelerate():
    import pandas as pd
    from parity import compare_frames
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.plan import (
        CpuGenerate, CpuSource, ExecutionPlanCapture, accelerate, collect)
    df = pd.DataFrame({
        "k": np.array([1, 2, 3], np.int64),
        "x": np.array([1.5, 2.5, 3.5]),
        "y": np.array([10.0, 20.0, 30.0]),
    })
    src = CpuSource.from_pandas(df, num_partitions=1)
    plan = CpuGenerate([col("x"), col("y")], src, include_pos=True,
                       value_name="val", retained=["k"])
    expected = plan.collect()
    got = collect(accelerate(plan, C.RapidsConf()))
    assert len(expected) == 6
    ExecutionPlanCapture.assert_contains_tpu("GenerateExec")
    compare_frames(expected, got, "posexplode")


def test_cpu_expand_fallback_on_unsupported_expr():
    """An expand whose projection uses an unsupported expression falls
    back to the CPU golden engine (plan-time tagging, not runtime
    raise)."""
    import pandas as pd
    from parity import compare_frames
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs.base import col, Literal
    from spark_rapids_tpu.plan import (
        CpuExpand, CpuSource, ExecutionPlanCapture, accelerate, collect)
    df = pd.DataFrame({"a": np.array([1, 2], np.int64)})
    src = CpuSource.from_pandas(df, num_partitions=1)

    class _Mystery(type(col("a"))):  # unregistered expression type
        pass
    mystery = _Mystery("a")
    plan = CpuExpand([[col("a")], [mystery]], ["a"], src)
    expected = plan.collect()
    got = collect(accelerate(plan, C.RapidsConf()))
    ExecutionPlanCapture.assert_did_fall_back("CpuExpand")
    compare_frames(expected, got, "expand-fallback")
