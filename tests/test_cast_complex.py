"""Gated cast directions + complex-type extractors:
float<->string casts behind per-direction flags (reference
GpuCast.scala:31), string->timestamp/bool, StringSplit consumed by
GetArrayItem (stringFunctions.scala:812), GetArrayItem/GetMapValue over
inline constructors (complexTypeExtractors.scala:88).  Every gated
direction must TAG at plan time when disabled — never raise at runtime."""
import numpy as np
import pandas as pd
import pytest

from parity import compare_frames
from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import Alias, col, Literal
from spark_rapids_tpu.exprs.cast import Cast
from spark_rapids_tpu.plan import (
    CpuProject, CpuSource, ExecutionPlanCapture, accelerate, collect)


def conf(**kv):
    return C.RapidsConf({k.replace("__", "."): v for k, v in kv.items()})


def _run(plan, c):
    expected = plan.collect()
    got = collect(accelerate(plan, c))
    compare_frames(expected, got)
    return expected


# -- float -> string --------------------------------------------------------
FLOATS = [1.0, 0.1, -2.5, 1234567.0, 1e7, 0.001, 1e-4,
          float("inf"), -float("inf"), 0.0, -0.0, 3.14159, 123.456,
          2.5e-10, 6.02214076e23]


def test_float_to_string_gated_on():
    # plain float64 column: NaN would become null at the source boundary
    # (from_pandas contract), so NaN-as-value is covered by the kernel
    # smoke tests, not the planner path
    src = CpuSource.from_pandas(
        pd.DataFrame({"v": np.array(FLOATS, np.float64)}))
    plan = CpuProject([Alias(Cast(col("v"), T.STRING), "s")], src)
    c = conf(spark__rapids__sql__castFloatToString__enabled=True)
    expected = _run(plan, c)
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    # Java notation spot checks
    vals = list(expected["s"])
    assert vals[0] == "1.0" and vals[4] == "1.0E7" and vals[6] == "1.0E-4"
    assert vals[8] == "-Infinity" and vals[10] == "-0.0"
    assert vals[14] == "6.02214076E23"


def test_float_to_string_gated_off_falls_back():
    src = CpuSource.from_pandas(
        pd.DataFrame({"v": pd.array([1.5, None], "Float64")}))
    plan = CpuProject([Alias(Cast(col("v"), T.STRING), "s")], src)
    _run(plan, conf())  # default: disabled
    ExecutionPlanCapture.assert_did_fall_back("CpuProject")


def test_int_to_string_not_gated():
    src = CpuSource.from_pandas(
        pd.DataFrame({"v": pd.array([0, -7, 123, None], "Int64")}))
    plan = CpuProject([Alias(Cast(col("v"), T.STRING), "s")], src)
    _run(plan, conf())
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")


# -- string -> float --------------------------------------------------------
def test_string_to_float_gated_on():
    vals = ["1.5", " 42 ", "-3.25e2", "1e-3", ".5", "1.", "inf",
            "-Infinity", "NaN", "abc", "", "1.2.3", "1e", "0.1", None]
    src = CpuSource.from_pandas(pd.DataFrame({"s": vals}))
    plan = CpuProject([Alias(Cast(col("s"), T.FLOAT64), "v")], src)
    c = conf(spark__rapids__sql__castStringToFloat__enabled=True)
    expected = _run(plan, c)
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    assert pd.isna(expected["v"][9]) and float(expected["v"][3]) == 0.001


def test_string_to_float_gated_off_falls_back():
    src = CpuSource.from_pandas(pd.DataFrame({"s": ["1.5", None]}))
    plan = CpuProject([Alias(Cast(col("s"), T.FLOAT64), "v")], src)
    _run(plan, conf())
    ExecutionPlanCapture.assert_did_fall_back("CpuProject")


# -- string -> bool / timestamp --------------------------------------------
def test_string_to_bool():
    vals = ["true", "FALSE", " t ", "no", "Y", "1", "0", "maybe", "", None]
    src = CpuSource.from_pandas(pd.DataFrame({"s": vals}))
    plan = CpuProject([Alias(Cast(col("s"), T.BOOL), "b")], src)
    expected = _run(plan, conf())
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    assert expected["b"][0] == True and expected["b"][1] == False  # noqa
    assert pd.isna(expected["b"][7])


def test_string_to_timestamp_gated():
    vals = ["2020-03-01", "2020-03-01 12:34:56", "2020-03-01 12:34:56.5",
            "2020-03-01 12:34:56.123456", "2020-13-01", "2020-02-30",
            "2020-03-01 25:00:00", "nope", None]
    src = CpuSource.from_pandas(pd.DataFrame({"s": vals}))
    plan = CpuProject([Alias(Cast(col("s"), T.TIMESTAMP_US), "t")], src)
    c = conf(spark__rapids__sql__castStringToTimestamp__enabled=True)
    expected = _run(plan, c)
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    assert int(expected["t"][1]) - int(expected["t"][0]) == \
        (12 * 3600 + 34 * 60 + 56) * 1000000
    assert int(expected["t"][2]) - int(expected["t"][1]) == 500000
    for i in (4, 5, 6, 7):
        assert pd.isna(expected["t"][i])

    _run(plan, conf())
    ExecutionPlanCapture.assert_did_fall_back("CpuProject")


# -- split()[i] -------------------------------------------------------------
def _split_df():
    return pd.DataFrame({"s": ["a,b,c", "x", "", ",lead", "trail,", ",,",
                               "a,,c", None]})


@pytest.mark.parametrize("idx", [0, 1, 2, 5])
def test_string_split_index_parity(idx):
    from spark_rapids_tpu.exprs.complex import GetArrayItem
    from spark_rapids_tpu.exprs.string_fns import StringSplit
    src = CpuSource.from_pandas(_split_df())
    plan = CpuProject([Alias(GetArrayItem(
        StringSplit(col("s"), Literal(",", T.STRING)),
        Literal(idx, T.INT32)), "p")], src)
    _run(plan, conf())
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")


def test_string_split_multichar_delim():
    from spark_rapids_tpu.exprs.complex import GetArrayItem
    from spark_rapids_tpu.exprs.string_fns import StringSplit
    src = CpuSource.from_pandas(pd.DataFrame(
        {"s": ["a::b::c", "::x", "aa:a::b", "::::"]}))
    for idx in (0, 1, 2):
        plan = CpuProject([Alias(GetArrayItem(
            StringSplit(col("s"), Literal("::", T.STRING)),
            Literal(idx, T.INT32)), "p")], src)
        _run(plan, conf())
        ExecutionPlanCapture.assert_contains_tpu("ProjectExec")


def test_string_split_positive_limit():
    from spark_rapids_tpu.exprs.complex import GetArrayItem
    from spark_rapids_tpu.exprs.string_fns import StringSplit
    src = CpuSource.from_pandas(_split_df())
    plan = CpuProject([Alias(GetArrayItem(
        StringSplit(col("s"), Literal(",", T.STRING),
                    Literal(2, T.INT32)),
        Literal(1, T.INT32)), "p")], src)
    _run(plan, conf())
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")


def test_string_split_regex_pattern_falls_back():
    from spark_rapids_tpu.exprs.complex import GetArrayItem
    from spark_rapids_tpu.exprs.string_fns import StringSplit
    src = CpuSource.from_pandas(pd.DataFrame({"s": ["a1b22c"]}))
    plan = CpuProject([Alias(GetArrayItem(
        StringSplit(col("s"), Literal(r"\d+", T.STRING)),
        Literal(0, T.INT32)), "p")], src)
    got = collect(accelerate(plan, conf()))
    ExecutionPlanCapture.assert_did_fall_back("CpuProject")
    assert list(got["p"]) == ["a"]  # CPU golden runs the real regex


# -- inline array / map -----------------------------------------------------
def test_get_array_item_inline():
    from spark_rapids_tpu.exprs.complex import CreateArray, GetArrayItem
    src = CpuSource.from_pandas(pd.DataFrame({
        "a": pd.array([1, 2, None], "Int64"),
        "b": pd.array([10, 20, 30], "Int64"),
        "i": pd.array([0, 1, 5], "Int32")}))
    plan = CpuProject([Alias(GetArrayItem(
        CreateArray((col("a"), col("b"))), col("i")), "v")], src)
    expected = _run(plan, conf())
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    assert list(expected["v"][:2]) == [1, 20]
    assert pd.isna(expected["v"][2])  # out of range -> null


def test_get_map_value_inline():
    from spark_rapids_tpu.exprs.complex import CreateMap, GetMapValue
    src = CpuSource.from_pandas(pd.DataFrame({
        "k": ["x", "y", "z", None]}))
    plan = CpuProject([Alias(GetMapValue(
        CreateMap((Literal("x", T.STRING), Literal(1, T.INT64),
                   Literal("y", T.STRING), Literal(2, T.INT64))),
        col("k")), "v")], src)
    expected = _run(plan, conf())
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    assert list(expected["v"][:2]) == [1, 2]
    assert pd.isna(expected["v"][2]) and pd.isna(expected["v"][3])


def test_bare_split_falls_back():
    from spark_rapids_tpu.exprs.string_fns import StringSplit
    src = CpuSource.from_pandas(pd.DataFrame({"s": ["a,b"]}))
    plan = CpuProject([Alias(
        StringSplit(col("s"), Literal(",", T.STRING)), "p")], src)
    tpu = accelerate(plan, conf())
    ExecutionPlanCapture.assert_did_fall_back("CpuProject")


def test_float32_to_string_parity():
    src = CpuSource.from_pandas(pd.DataFrame(
        {"v": np.array([0.1, 3.14, -2.5, 1e10, 0.001], np.float32)}))
    plan = CpuProject([Alias(Cast(col("v"), T.STRING), "s")], src)
    c = conf(spark__rapids__sql__castFloatToString__enabled=True)
    expected = _run(plan, c)
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    assert list(expected["s"])[:2] == ["0.1", "3.14"]


def test_string_to_float_review_regressions():
    """r2 code-review cases: leading zeros don't eat the digit budget,
    long/padded exponents saturate like Java, tabs trim like Spark."""
    vals = ["0000000000000000001.5", "0.00000000000000000012345",
            "1e0005", "1E+0010", "1e99999", "1e-99999", "\t1.5 ",
            " 0.0001"]
    src = CpuSource.from_pandas(pd.DataFrame({"s": vals}))
    plan = CpuProject([Alias(Cast(col("s"), T.FLOAT64), "v")], src)
    c = conf(spark__rapids__sql__castStringToFloat__enabled=True)
    expected = _run(plan, c)
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    got = [float(v) for v in expected["v"]]
    assert got[0] == 1.5 and got[1] == 1.2345e-19
    assert got[2] == 1e5 and got[3] == 1e10
    assert got[4] == float("inf") and got[5] == 0.0
    assert got[6] == 1.5 and got[7] == 1e-4


def test_string_to_timestamp_trims():
    vals = [" 2020-03-01", "2020-03-01 12:34:56  ", "\t2020-01-01"]
    src = CpuSource.from_pandas(pd.DataFrame({"s": vals}))
    plan = CpuProject([Alias(Cast(col("s"), T.TIMESTAMP_US), "t")], src)
    c = conf(spark__rapids__sql__castStringToTimestamp__enabled=True)
    expected = _run(plan, c)
    ExecutionPlanCapture.assert_contains_tpu("ProjectExec")
    assert not expected["t"].isna().any()
