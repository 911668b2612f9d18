"""The ungrouped aggregate (`HashAggregateExec` without group keys) as a
plain masked reduction: every function's update and merge over the input
shapes a plan hands it, against numpy by hand and against the CPU plan's
own aggregate (`plan/nodes.CpuAggregate` over `plan/cpu_eval.py`), and
the structure of the reduce kernel itself (no scan: its size does not
grow with the batch)."""
import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import MIN_CAPACITY
from spark_rapids_tpu.exec.aggregate import AggMode, HashAggregateExec
from spark_rapids_tpu.exec.basic import (CoalescePartitionsExec, FilterExec,
                                         LocalBatchSource)
from spark_rapids_tpu.exprs.aggregates import (
    Average, Count, CountStar, First, Last, Max, Min, StddevSamp, Sum,
    VarianceSamp)
from spark_rapids_tpu.exprs.base import col, lit

SCHEMA = T.Schema.of(("i", T.INT32), ("w", T.INT64), ("f", T.FLOAT64),
                     ("n", T.FLOAT64), ("s", T.STRING), ("keep", T.INT32))
VALUE_COLUMNS = ("i", "w", "f", "n", "s")
WORDS = np.array(["pear", "apple", "fig", "applesauce", "", "zebra", "Fig"],
                 dtype=object)


def _rows(rng, n, nulls):
    """`n` rows of every column and their validity; `w` sits near 2**62
    so that a handful of rows wrap an INT64 sum, `n` carries NaNs."""
    data = {
        "i": rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32),
        "w": (2 ** 62 + rng.integers(0, 1000, n)).astype(np.int64),
        "f": np.round(rng.uniform(-5e4, 1e5, n), 2),
        "n": np.where(rng.random(n) < 0.25, np.nan, rng.normal(size=n)),
        "s": WORDS[rng.integers(0, len(WORDS), n)],
        "keep": np.ones(n, np.int32),
    }
    valid = {c: (rng.random(n) > 0.3 if nulls else np.ones(n, bool))
             for c in VALUE_COLUMNS}
    valid["keep"] = np.ones(n, bool)
    return data, valid


def _batch(data, valid):
    return ColumnarBatch.from_numpy(data, SCHEMA, validity=valid)


def _kept(chunks):
    """The rows a scenario's aggregate sees, in order: {column: (values,
    validity)} over the chunks' rows whose `keep` is set."""
    out = {}
    for c in VALUE_COLUMNS:
        vals = np.concatenate([d[c][d["keep"] > 0] for d, _ in chunks])
        ok = np.concatenate([v[c][d["keep"] > 0] for d, v in chunks])
        out[c] = (vals, ok)
    return out


def _scenario(name):
    """(source exec, kept rows) of one input shape."""
    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "empty_input":
        return LocalBatchSource([[]], schema=SCHEMA), _kept(
            [_rows(rng, 0, False)])
    if name == "all_valid":
        chunks = [_rows(rng, 50, False)]
    elif name == "nulls":
        chunks = [_rows(rng, 61, True)]
    elif name in ("several_batches", "partial_final"):
        chunks = [_rows(rng, n, True) for n in (40, 7, 33, 64)]
    elif name == "filter_drops_batch":
        chunks = [_rows(rng, n, True) for n in (30, 45, 20)]
        chunks[1][0]["keep"][:] = 0
        chunks[0][0]["keep"][::3] = 0
    elif name in ("filter_drops_first_batch", "filter_drops_last_batch"):
        chunks = [_rows(rng, n, True) for n in (30, 45, 20)]
        chunks[0 if "first" in name else 2][0]["keep"][:] = 0
        # the first and the last row that are kept hold a value
        for c in VALUE_COLUMNS:
            chunks[1][1][c][[0, -1]] = True
            chunks[0][1][c][0] = chunks[2][1][c][-1] = True
    elif name == "sparse":
        chunks = [_rows(rng, 48, True)]
        chunks[0][0]["keep"][rng.random(48) < 0.5] = 0
    else:
        raise AssertionError(name)
    batches = [_batch(d, v) for d, v in chunks]
    if name == "sparse":
        (b,), ((d, _),) = batches, chunks
        mask = np.zeros(b.capacity, bool)
        mask[:len(d["keep"])] = d["keep"] > 0
        src = LocalBatchSource([[ColumnarBatch(
            SCHEMA, b.columns, None, (), sparse=jnp.asarray(mask))]])
    elif name == "partial_final":
        src = LocalBatchSource([batches[:2], batches[2:]])
    else:
        src = LocalBatchSource([batches])
    if name.startswith("filter_drops"):
        src = FilterExec(col("keep") > lit(0), src)
    return src, _kept(chunks)


# -- the functions, each with its expectation by hand -----------------------
def _valid(kept, c):
    vals, ok = kept[c]
    return vals[ok]


def _sum_wrapping(kept, c):
    v = _valid(kept, c)
    if not len(v):
        return None
    with np.errstate(over="ignore"):
        return int(np.sum(v.astype(np.int64), dtype=np.int64))


def _sum_float(kept, c):
    v = _valid(kept, c)
    return float(np.sum(v)) if len(v) else None


def _spark_min(kept, c):
    """Spark's float ordering: NaN is the largest value."""
    v = _valid(kept, c)
    if not len(v):
        return None
    if v.dtype.kind == "f":
        rest = v[~np.isnan(v)]
        return float(rest.min()) if len(rest) else float("nan")
    return v.min()


def _spark_max(kept, c):
    v = _valid(kept, c)
    if not len(v):
        return None
    if v.dtype.kind == "f":
        return float("nan") if np.isnan(v).any() else float(v.max())
    return v.max()


def _string_extreme(kept, c, pick):
    v = _valid(kept, c)
    return pick(v, key=lambda x: x.encode()) if len(v) else None


def _first_last(kept, c, first, ignore_nulls):
    vals, ok = kept[c]
    if ignore_nulls:
        vals, ok = vals[ok], ok[ok]
    if not len(vals):
        return None
    at = 0 if first else -1
    return vals[at] if ok[at] else None


def _moment(kept, c, fn):
    v = _valid(kept, c)
    return float(fn(v, ddof=1)) if len(v) > 1 else None


def _mean(kept, c):
    v = _valid(kept, c)
    return float(np.mean(v)) if len(v) else None


#: name -> (aggregate, expectation(kept) -> python value or None, rtol)
FUNCTIONS = {
    "sum_int32": (Sum(col("i")), lambda k: _sum_wrapping(k, "i"), 0),
    "sum_int64_wraps": (Sum(col("w")), lambda k: _sum_wrapping(k, "w"), 0),
    "sum_float64": (Sum(col("f")), lambda k: _sum_float(k, "f"), 1e-13),
    "count": (Count(col("f")), lambda k: int(k["f"][1].sum()), 0),
    "count_star": (CountStar(), lambda k: len(k["f"][0]), 0),
    "min_int": (Min(col("i")), lambda k: _spark_min(k, "i"), 0),
    "max_int": (Max(col("i")), lambda k: _spark_max(k, "i"), 0),
    "min_float_nan": (Min(col("n")), lambda k: _spark_min(k, "n"), 0),
    "max_float_nan": (Max(col("n")), lambda k: _spark_max(k, "n"), 0),
    "max_float": (Max(col("f")), lambda k: _spark_max(k, "f"), 0),
    "min_string": (Min(col("s")), lambda k: _string_extreme(k, "s", min), 0),
    "max_string": (Max(col("s")), lambda k: _string_extreme(k, "s", max), 0),
    "average": (Average(col("f")), lambda k: _mean(k, "f"), 1e-13),
    "first": (First(col("i")),
              lambda k: _first_last(k, "i", True, False), 0),
    "first_ignore_nulls": (First(col("i"), ignore_nulls=True),
                           lambda k: _first_last(k, "i", True, True), 0),
    "last": (Last(col("f")),
             lambda k: _first_last(k, "f", False, False), 0),
    "last_ignore_nulls": (Last(col("f"), ignore_nulls=True),
                          lambda k: _first_last(k, "f", False, True), 0),
    "last_string": (Last(col("s"), ignore_nulls=True),
                    lambda k: _first_last(k, "s", False, True), 0),
    "var_samp": (VarianceSamp(col("f")),
                 lambda k: _moment(k, "f", np.var), 1e-10),
    "stddev_samp": (StddevSamp(col("f")),
                    lambda k: _moment(k, "f", np.std), 1e-10),
}
SCENARIOS = ("all_valid", "nulls", "filter_drops_batch", "empty_input",
             "several_batches", "partial_final", "sparse")


def _aggregates():
    return [f.alias(name) for name, (f, _, _) in FUNCTIONS.items()]


_RESULTS: dict = {}


def _run(scenario):
    """One execution per scenario, every function in it (they share the
    kernel's scan rounds, as the functions of a query do); each
    parametrised case below reads its own column."""
    if scenario not in _RESULTS:
        src, kept = _scenario(scenario)
        if scenario == "partial_final":
            partial = HashAggregateExec([], _aggregates(), src,
                                        mode=AggMode.PARTIAL)
            plan = HashAggregateExec([], _aggregates(),
                                     CoalescePartitionsExec(1, partial),
                                     mode=AggMode.FINAL)
        else:
            plan = HashAggregateExec([], _aggregates(),
                                     CoalescePartitionsExec(1, src))
        out = plan.collect()
        assert out.num_rows == 1
        assert plan._lane == "reduce"
        _RESULTS[scenario] = (
            {name: out.column(name).to_pylist(1)[0] for name in FUNCTIONS},
            kept)
    return _RESULTS[scenario]


def _same(got, want, rtol):
    if want is None:
        return got is None
    if got is None:
        return False
    if isinstance(want, float) and np.isnan(want):
        return isinstance(got, float) and np.isnan(got)
    if rtol:
        return bool(np.isclose(got, want, rtol=rtol, atol=0.0))
    return got == want


@pytest.mark.parametrize("scenario", SCENARIOS)
@pytest.mark.parametrize("function", list(FUNCTIONS))
def test_ungrouped_function(function, scenario):
    got, kept = _run(scenario)
    _, expect, rtol = FUNCTIONS[function]
    want = expect(kept)
    if isinstance(want, np.generic):
        want = want.item()
    assert _same(got[function], want, rtol), (got[function], want)


#: older than the plain reduction (PERF.md section 7): the emptied
#: batch's partial is a null row, and the one-column intermediate cannot
#: tell it from a null first (last) value
_EMPTIED_END = pytest.mark.xfail(
    strict=True, reason="ungrouped First / Last that respect nulls answer "
    "null when a filter emptied the input's first / last batch")


@pytest.mark.parametrize("function,scenario", [
    pytest.param("first", "filter_drops_first_batch", marks=_EMPTIED_END),
    pytest.param("last", "filter_drops_last_batch", marks=_EMPTIED_END),
    ("first", "filter_drops_last_batch"),
    ("last", "filter_drops_first_batch"),
    ("first_ignore_nulls", "filter_drops_first_batch"),
    ("last_ignore_nulls", "filter_drops_last_batch"),
    ("last_string", "filter_drops_last_batch"),
])
def test_first_last_when_a_filter_empties_an_end_batch(function, scenario):
    test_ungrouped_function(function, scenario)


@pytest.mark.parametrize("scenario", ["all_valid", "nulls",
                                      "several_batches"])
def test_matches_the_cpu_plan(scenario):
    """The same aggregates through the CPU plan's reduction
    (`CpuAggregate`: `cpu_eval` for the inputs, pandas for the totals).
    pandas has no Spark NaN ordering and its own string ordering agrees
    on these words, so the NaN cases are left to the hand expectations
    above."""
    from spark_rapids_tpu.plan.nodes import CpuAggregate, CpuSource
    got, kept = _run(scenario)
    frame = pd.DataFrame({
        c: pd.array([v if o else None for v, o in zip(*kept[c])],
                    dtype={"i": "Int32", "w": "Int64", "f": "Float64",
                           "n": "Float64", "s": "string"}[c])
        for c in VALUE_COLUMNS})
    names = [n for n in FUNCTIONS
             if "nan" not in n and n != "sum_int64_wraps"]
    aggs = [FUNCTIONS[n][0].alias(n) for n in names]
    schema = T.Schema(tuple(f for f in SCHEMA.fields
                            if f.name in VALUE_COLUMNS))
    (part,) = CpuAggregate([], aggs, CpuSource([frame], schema)).execute()
    (cpu,) = list(part)
    for n in names:
        want = cpu[n][0]
        want = None if want is pd.NA or want is None else want
        if isinstance(want, np.generic):
            want = want.item()
        rtol = FUNCTIONS[n][2] or (1e-13 if isinstance(want, float) else 0)
        assert _same(got[n], want, rtol), (n, got[n], want)


# -- the kernel's structure --------------------------------------------------
def _count_equations(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count_equations(inner)
    return n


def _q6_shaped(rows):
    """q6's aggregate (a FLOAT64 sum and its count companion) over one
    batch of `rows` rows, and that batch."""
    rng = np.random.default_rng(rows)
    b = ColumnarBatch.from_numpy(
        {"price": rng.uniform(900.0, 1e5, rows),
         "discount": rng.uniform(0.0, 0.1, rows)})
    plan = HashAggregateExec(
        [], [Sum(col("price") * col("discount")).alias("revenue")],
        LocalBatchSource([[b]]))
    return plan, b


#: equations of q6's reduce kernel whatever its rows (18 when this was
#: written, the jitted call itself among them; the one-segment
#: `_segscan` it replaces had 772 at 65,536 rows for its two operands
#: and grew with log2 of the capacity)
EQUATION_CEILING = 40


def test_reduce_kernel_does_not_grow_with_its_rows():
    counts = {}
    for rows in (1024, 65536):
        plan, b = _q6_shaped(rows)
        assert b.capacity == rows
        kern = plan._reduce_kernel(b, "update")
        jaxpr = jax.make_jaxpr(kern)(b.columns, b.num_rows_i32)
        counts[rows] = _count_equations(jaxpr.jaxpr)
        outs = kern(b.columns, b.num_rows_i32)
        assert {c.capacity for c in outs} == {MIN_CAPACITY}
    assert counts[1024] == counts[65536] < EQUATION_CEILING, counts


@pytest.mark.parametrize("phase", ["update", "merge"])
def test_partials_are_one_row_at_the_smallest_capacity(phase):
    plan, b = _q6_shaped(4096)
    schema = plan._partial_schema()
    partial = ColumnarBatch(schema, list(plan._reduce_kernel(b, "update")(
        b.columns, b.num_rows_i32)), 1)
    if phase == "update":
        assert partial.capacity == MIN_CAPACITY
        return
    # 40 equal partials (a 64-slot concat) merged: 40 times one total
    merged = plan._merge_reduction([partial] * 40, schema)
    assert merged.capacity == MIN_CAPACITY and merged.num_rows == 1
    one = partial.columns[0].to_pylist(1)[0]
    got = merged.columns[0].to_pylist(1)[0]
    assert np.isclose(got, 40 * one, rtol=1e-14, atol=0.0)
