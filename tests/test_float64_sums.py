"""A grouped Sum / Average of a FLOAT64 column is float64 whatever the
lane switches say.

`bandedGroupby.enabled` and `dictGroupby.enabled` (both default true)
choose a lane, and those two lanes accumulate in float32 on the MXU; a
FLOAT64 measure therefore never takes them (exec/aggregate.py chooses
from the measure's type).  The tests hold the default conf to SQL's
answer on TPC-H q3 and q1 against a plain float64 pandas reference that
imports nothing of the program, show that the same reference computed
in float32 does NOT pass the same limit (so the limit tells the two
apart), and hold FLOAT32 / integral measures and Count to the fast
lanes they had.
"""
from __future__ import annotations

import datetime as _dt

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.aggregate import AggMode, HashAggregateExec
from spark_rapids_tpu.exec.basic import LocalBatchSource
from spark_rapids_tpu.exprs.aggregates import Average, Count, Sum
from spark_rapids_tpu.exprs.base import col

LIMIT = 1e-10
SCALE = 20_000
#: the conf the reference's TPC harness runs, with no lane switch set
DEFAULTS = {"spark.rapids.sql.variableFloatAgg.enabled": True,
            "spark.rapids.sql.incompatibleOps.enabled": True,
            "spark.rapids.sql.test.enabled": True}
LANES_OFF = dict(DEFAULTS, **{
    "spark.rapids.tpu.bandedGroupby.enabled": False,
    "spark.rapids.tpu.dictGroupby.enabled": False})


def _days(s):
    return (_dt.date.fromisoformat(s) - _dt.date(1970, 1, 1)).days


# ---- plain references, from the queries' text ---------------------------
def ref_q3(t) -> pd.DataFrame:
    cust = t["customer"]
    cust = cust[cust.c_mktsegment == "BUILDING"][["c_custkey"]]
    orders = t["orders"]
    orders = orders[orders.o_orderdate < _days("1995-03-15")][
        ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]]
    li = t["lineitem"]
    li = li[li.l_shipdate > _days("1995-03-15")][
        ["l_orderkey", "l_extendedprice", "l_discount"]]
    j = cust.merge(orders, left_on="c_custkey", right_on="o_custkey")
    j = j.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j["revenue"] = j.l_extendedprice * (1.0 - j.l_discount)
    out = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                    sort=False).agg(revenue=("revenue", "sum"))
    return out.reset_index()


def ref_q1(t) -> pd.DataFrame:
    li = t["lineitem"]
    li = li[li.l_shipdate <= _days("1998-09-02")]
    disc_price = li.l_extendedprice * (1.0 - li.l_discount)
    rows = pd.DataFrame({
        "l_returnflag": li.l_returnflag, "l_linestatus": li.l_linestatus,
        "qty": li.l_quantity, "price": li.l_extendedprice,
        "disc_price": disc_price, "charge": disc_price * (1.0 + li.l_tax),
        "disc": li.l_discount})
    out = rows.groupby(["l_returnflag", "l_linestatus"], sort=True).agg(
        sum_qty=("qty", "sum"), sum_base_price=("price", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("qty", "mean"), avg_price=("price", "mean"),
        avg_disc=("disc", "mean"), count_order=("qty", "size"))
    return out.reset_index()


REFS = {3: (ref_q3, ["l_orderkey", "o_orderdate", "o_shippriority"]),
        1: (ref_q1, ["l_returnflag", "l_linestatus"])}


def in_float32(tables: dict) -> dict:
    """The tables with every float column at float32: the reference's
    arithmetic and sums are then float32 too."""
    return {name: pd.DataFrame(
        {c: df[c].astype(np.float32) if df[c].dtype.kind == "f" else df[c]
         for c in df.columns}) for name, df in tables.items()}


def float_rel_err(got: pd.DataFrame, want: pd.DataFrame, keys) -> float:
    """The largest relative gap of a float cell, rows matched on the
    keys; every other cell has to be equal."""
    want = want.copy()
    want.columns = list(got.columns)[:len(want.columns)]
    keys = list(got.columns)[:len(keys)]
    m = got.merge(want, on=keys, suffixes=("", "_want"))
    assert len(m) == len(got)       # q3's ten are ten of the groups
    worst = 0.0
    for c in got.columns[len(keys):]:
        a = m[c].to_numpy()
        b = m[c + "_want"].to_numpy()
        if a.dtype.kind == "f" or b.dtype.kind == "f":
            worst = max(worst, float(np.max(
                np.abs(a.astype(np.float64) - b.astype(np.float64))
                / np.abs(b.astype(np.float64)))))
        else:
            assert (a == b).all(), c
    return worst


@pytest.fixture(scope="module")
def tables():
    from benchmark.gen import tpch
    return {seed: tpch.generate(seed, SCALE) for seed in (2 ** 31 + 7, 19)}


def run_query(query: int, tables: dict, settings: dict) -> pd.DataFrame:
    from spark_rapids_tpu.models.tpch_data import sources
    from spark_rapids_tpu.models.tpch_queries import QUERIES
    from spark_rapids_tpu.plan.overrides import accelerate, collect
    conf = C.RapidsConf(settings)

    def sub(plan):
        return collect(accelerate(plan, conf), conf)
    plan = accelerate(QUERIES[query](sources(tables, 2), sub), conf)
    return collect(plan, conf)


def answer(query, tables, settings):
    got = run_query(query, tables, settings)
    assert len(got) == (10 if query == 3 else 4)
    return got


# ---- q3 and q1 under the default conf ------------------------------------
@pytest.mark.parametrize("seed", [2 ** 31 + 7, 19])
@pytest.mark.parametrize("query", [3, 1])
def test_default_conf_agrees_with_the_float64_reference(query, seed, tables):
    ref, keys = REFS[query]
    got = answer(query, tables[seed], DEFAULTS)
    want = ref(tables[seed])
    assert float_rel_err(got, want, keys) < LIMIT
    if query == 3:      # the ten largest, in order
        top = want.sort_values(["revenue", "o_orderdate"],
                               ascending=[False, True]).head(10)
        np.testing.assert_allclose(got["revenue"].to_numpy(),
                                   top["revenue"].to_numpy(), rtol=LIMIT)


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 19])
@pytest.mark.parametrize("query", [3, 1])
def test_the_float32_reference_does_not_pass_the_limit(query, seed, tables):
    """The control: at these magnitudes (prices to 1e5, sums to 1e8) a
    float32 computation is 1e-8 to 1e-7 off, so the limit would catch a
    lane that accumulated in float32."""
    ref, keys = REFS[query]
    t = tables[seed]
    low = ref(in_float32(t))
    err = float_rel_err(low.astype({c: np.float64 for c in low.columns
                                    if low[c].dtype.kind == "f"}),
                        ref(t), keys)
    assert 1e-9 < err < 1e-5


@pytest.mark.parametrize("query", [3, 1])
def test_the_lane_switches_do_not_change_the_answer(query, tables):
    t = tables[19]
    on = answer(query, t, DEFAULTS)
    off = answer(query, t, LANES_OFF)
    pd.testing.assert_frame_equal(on, off, check_exact=True)


# ---- the lane each measure type takes ------------------------------------
N = 4096
#: a key range past dictGroupby.maxGroups: the dictionary lane declines
#: at run time and the banded lane is next
WIDE = 1_000_003


def _agg(values, dtype, funcs, wide: bool, mode=AggMode.COMPLETE):
    rng = np.random.default_rng(5)
    batches = []
    for _ in range(2):              # two batches: a merge phase follows
        k = rng.integers(0, 50, N).astype(np.int64)
        batches.append(ColumnarBatch.from_numpy(
            {"k": k * WIDE if wide else k,
             "v": values(rng).astype(dtype)}))
    return HashAggregateExec([col("k")], funcs,
                             LocalBatchSource([batches]), mode=mode), batches


def _frames(batches):
    return pd.concat([b.to_pandas() for b in batches], ignore_index=True)


MEASURES = {
    "float64": (lambda r: r.uniform(1e4, 1e5, N), np.float64),
    "float32": (lambda r: r.uniform(1.0, 10.0, N), np.float32),
    "int32": (lambda r: r.integers(-1000, 1000, N), np.int32),
    "int64": (lambda r: r.integers(-1000, 1000, N), np.int64),
}
#: (update lane, merge lane) by measure and by whether the keys fit the
#: dictionary.  A Sum's intermediate is FLOAT64 for a float input, so a
#: FLOAT32 measure merges on the sort-segment lane; INT64 intermediates
#: stay on the banded lane.
EXPECTED = {
    ("float64", False): ("few-or-sort", "few-or-sort"),
    ("float64", True): ("few-or-sort", "few-or-sort"),
    ("float32", False): ("dict", "few-or-sort"),
    ("float32", True): ("banded", "few-or-sort"),
    ("int32", False): ("dict", "banded"),
    ("int32", True): ("banded", "banded"),
    ("int64", False): ("dict", "banded"),
    ("int64", True): ("banded", "banded"),
}


@pytest.mark.parametrize("measure,wide", sorted(EXPECTED))
def test_lane_by_measure_type_update_and_merge(measure, wide):
    values, dtype = MEASURES[measure]
    agg, batches = _agg(values, dtype, [Sum(col("v")).alias("s"),
                                       Count(col("v")).alias("c")], wide)
    with C.session(C.RapidsConf(DEFAULTS)):
        got = agg.collect().to_pandas().sort_values("k", ignore_index=True)
    assert (agg._lane, agg._merge_exec._lane) == EXPECTED[measure, wide]
    df = _frames(batches)
    want = df.astype({"v": np.float64 if dtype == np.float32 else dtype}
                     ).groupby("k").agg(s=("v", "sum"), c=("v", "size")
                                        ).reset_index()
    assert (got["k"].to_numpy() == want["k"].to_numpy()).all()
    assert (got["c"].to_numpy() == want["c"].to_numpy()).all()
    if dtype in (np.int32, np.int64):
        assert (got["s"].to_numpy() == want["s"].to_numpy()).all()
    else:
        np.testing.assert_allclose(
            got["s"].to_numpy(), want["s"].to_numpy(),
            rtol=1e-13 if dtype == np.float64 else 1e-5)


@pytest.mark.parametrize("func", ["sum", "avg"])
@pytest.mark.parametrize("wide", [False, True])
def test_float64_sum_and_average_read_the_same_with_the_lanes_off(func, wide):
    values, dtype = MEASURES["float64"]
    f = Sum if func == "sum" else Average
    out = []
    for settings in (DEFAULTS, LANES_OFF):
        agg, batches = _agg(values, dtype, [f(col("v")).alias("a")], wide)
        with C.session(C.RapidsConf(settings)):
            out.append(agg.collect().to_pandas().sort_values(
                "k", ignore_index=True))
        assert (agg._lane, agg._merge_exec._lane) == (
            "few-or-sort", "few-or-sort")
    pd.testing.assert_frame_equal(out[0], out[1], check_exact=True)
    want = _frames(batches).groupby("k").agg(
        a=("v", "sum" if func == "sum" else "mean")).reset_index()
    np.testing.assert_allclose(out[0]["a"].to_numpy(),
                               want["a"].to_numpy(), rtol=1e-13)


@pytest.mark.parametrize("measure", ["float32", "int64"])
def test_average_merges_its_float64_sum_in_float64(measure):
    """An Average's intermediate sum is FLOAT64 whatever it averages, so
    its merge phase never takes the float32 accumulator; its update
    phase keeps the fast lane."""
    values, dtype = MEASURES[measure]
    agg, batches = _agg(values, dtype, [Average(col("v")).alias("a")],
                        wide=True)
    with C.session(C.RapidsConf(DEFAULTS)):
        got = agg.collect().to_pandas().sort_values("k", ignore_index=True)
    assert (agg._lane, agg._merge_exec._lane) == ("banded", "few-or-sort")
    want = _frames(batches).astype({"v": np.float64}).groupby("k").agg(
        a=("v", "mean")).reset_index()
    np.testing.assert_allclose(got["a"].to_numpy(), want["a"].to_numpy(),
                               rtol=1e-13 if measure == "int64" else 1e-5)


def test_count_alone_keeps_the_fast_lanes():
    values, dtype = MEASURES["float64"]
    agg, batches = _agg(values, dtype, [Count(col("v")).alias("c")],
                        wide=True)
    with C.session(C.RapidsConf(DEFAULTS)):
        got = agg.collect().to_pandas().sort_values("k", ignore_index=True)
    assert (agg._lane, agg._merge_exec._lane) == ("banded", "banded")
    want = _frames(batches).groupby("k").agg(c=("v", "size")).reset_index()
    assert (got["c"].to_numpy() == want["c"].to_numpy()).all()
