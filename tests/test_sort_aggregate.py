"""Sort + aggregation parity tests against pandas (golden-rule harness per
SURVEY.md §4: same computation on CPU reference and TPU engine, diffed)."""
import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.aggregate import AggMode, HashAggregateExec
from spark_rapids_tpu.exec.basic import (CoalescePartitionsExec,
    LocalBatchSource)
from spark_rapids_tpu.exec.coalesce import CoalesceBatchesExec
from spark_rapids_tpu.exec.base import TargetSize
from spark_rapids_tpu.exec.limit import GlobalLimitExec, LocalLimitExec
from spark_rapids_tpu.exec.sort import (
    SortExec, SortOrder, SortedTopNExec, asc, desc)
from spark_rapids_tpu.exprs.aggregates import (
    Average, Count, CountStar, First, Last, Max, Min, Sum)
from spark_rapids_tpu.exprs.base import col, lit


def _sales_df(rng, n=200):
    return pd.DataFrame({
        "store": rng.choice(["north", "south", "east"], n),
        "sku": rng.integers(0, 10, n).astype(np.int64),
        "qty": rng.integers(1, 100, n).astype(np.int64),
        "price": np.round(rng.uniform(0.5, 50.0, n), 2),
    })


def test_sort_single_key(rng):
    df = pd.DataFrame({"x": rng.integers(-50, 50, 100).astype(np.int64)})
    out = SortExec([asc(col("x"))],
                   LocalBatchSource.from_pandas(df)).to_pandas()
    assert out["x"].tolist() == sorted(df["x"].tolist())


def test_sort_desc_with_nulls(rng):
    vals = np.array([5, 1, 3, 0, 9], np.int64)
    valid = np.array([True, False, True, True, False])
    b = ColumnarBatch.from_numpy({"x": vals}, validity={"x": valid})
    out = SortExec([desc(col("x"))], LocalBatchSource([[b]])).collect()
    # valid values are {5, 3, 0}; rows 1 and 4 are null
    # desc -> nulls last (Spark default)
    assert out.column("x").to_pylist(5) == [5, 3, 0, None, None]
    out2 = SortExec([SortOrder(col("x"), ascending=True)],
                    LocalBatchSource([[b]])).collect()
    # asc -> nulls first
    assert out2.column("x").to_pylist(5) == [None, None, 0, 3, 5]


def test_sort_float_nan_ordering():
    b = ColumnarBatch.from_numpy(
        {"x": np.array([1.0, np.nan, -np.inf, 0.0, np.inf])})
    out = SortExec([asc(col("x"))], LocalBatchSource([[b]])).collect()
    got = out.column("x").to_pylist(5)
    assert got[0] == -np.inf and got[1] == 0.0 and got[2] == 1.0
    assert got[3] == np.inf and np.isnan(got[4])  # NaN sorts largest


def test_sort_two_keys_string_primary(rng):
    df = pd.DataFrame({
        "s": rng.choice(["bb", "a", "ccc", "ab"], 50),
        "v": rng.integers(0, 100, 50).astype(np.int64)})
    out = SortExec([asc(col("s")), desc(col("v"))],
                   LocalBatchSource.from_pandas(df)).to_pandas()
    expect = df.sort_values(["s", "v"], ascending=[True, False])
    assert out["s"].tolist() == expect["s"].tolist()
    assert out["v"].tolist() == expect["v"].tolist()


def test_groupby_sum_count_parity(rng):
    df = _sales_df(rng)
    plan = HashAggregateExec(
        [col("store")],
        [Sum(col("qty")).alias("total_qty"),
         Count(col("qty")).alias("n"),
         CountStar().alias("rows")],
        CoalescePartitionsExec(
            1, LocalBatchSource.from_pandas(df, num_partitions=3)))
    out = plan.to_pandas().sort_values("store").reset_index(drop=True)
    exp = (df.groupby("store")
           .agg(total_qty=("qty", "sum"), n=("qty", "count"),
                rows=("qty", "size"))
           .reset_index().sort_values("store").reset_index(drop=True))
    assert out["store"].tolist() == exp["store"].tolist()
    assert out["total_qty"].tolist() == exp["total_qty"].tolist()
    assert out["n"].tolist() == exp["n"].tolist()
    assert out["rows"].tolist() == exp["rows"].tolist()


def test_groupby_min_max_avg_parity(rng):
    df = _sales_df(rng)
    plan = HashAggregateExec(
        [col("store"), col("sku")],
        [Min(col("price")).alias("mn"), Max(col("price")).alias("mx"),
         Average(col("price")).alias("avg")],
        CoalescePartitionsExec(
            1, LocalBatchSource.from_pandas(df, num_partitions=4)))
    out = plan.to_pandas().sort_values(["store", "sku"]).reset_index(
        drop=True)
    exp = (df.groupby(["store", "sku"])["price"]
           .agg(mn="min", mx="max", avg="mean").reset_index()
           .sort_values(["store", "sku"]).reset_index(drop=True))
    assert out["store"].tolist() == exp["store"].tolist()
    assert out["sku"].tolist() == exp["sku"].tolist()
    np.testing.assert_allclose(out["mn"], exp["mn"])
    np.testing.assert_allclose(out["mx"], exp["mx"])
    np.testing.assert_allclose(out["avg"], exp["avg"], rtol=1e-12)


def test_groupby_with_nulls_in_keys_and_values():
    b = ColumnarBatch.from_numpy(
        {"k": np.array([1, 1, 2, 2, 0], np.int64),
         "v": np.array([10, 20, 30, 0, 50], np.int64)},
        validity={"k": np.array([True, True, True, True, False]),
                  "v": np.array([True, True, True, False, True])})
    plan = HashAggregateExec(
        [col("k")], [Sum(col("v")).alias("s"), Count(col("v")).alias("c")],
        LocalBatchSource([[b]]))
    out = plan.collect()
    rows = {k: (s, c) for k, s, c in zip(
        out.column("k").to_pylist(out.num_rows),
        out.column("s").to_pylist(out.num_rows),
        out.column("c").to_pylist(out.num_rows))}
    # null key forms its own group (SQL GROUP BY)
    assert rows[None] == (50, 1)
    assert rows[1] == (30, 2)
    assert rows[2] == (30, 1)  # null value ignored by sum/count


def test_groupby_all_null_group_sum_is_null():
    b = ColumnarBatch.from_numpy(
        {"k": np.array([7, 7], np.int64),
         "v": np.array([0, 0], np.int64)},
        validity={"v": np.array([False, False])})
    out = HashAggregateExec([col("k")], [Sum(col("v")).alias("s")],
                            LocalBatchSource([[b]])).collect()
    assert out.column("s").to_pylist(1) == [None]


def test_groupby_string_min_max(rng):
    df = pd.DataFrame({
        "g": rng.choice(["x", "y"], 40),
        "s": rng.choice(["apple", "pear", "fig", "kiwi", "zz"], 40)})
    out = HashAggregateExec(
        [col("g")], [Min(col("s")).alias("mn"), Max(col("s")).alias("mx")],
        LocalBatchSource.from_pandas(df)).to_pandas()
    out = out.sort_values("g").reset_index(drop=True)
    exp = df.groupby("g")["s"].agg(mn="min", mx="max").reset_index()
    assert out["mn"].tolist() == exp["mn"].tolist()
    assert out["mx"].tolist() == exp["mx"].tolist()


def test_reduction_no_keys(rng):
    df = _sales_df(rng, 100)
    out = HashAggregateExec(
        [], [Sum(col("qty")).alias("s"), CountStar().alias("n"),
             Min(col("price")).alias("mn")],
        CoalescePartitionsExec(
            1, LocalBatchSource.from_pandas(df, num_partitions=3))
    ).to_pandas()
    assert len(out) == 1
    assert out["s"][0] == df["qty"].sum()
    assert out["n"][0] == len(df)
    np.testing.assert_allclose(out["mn"][0], df["price"].min())


def test_reduction_empty_input():
    src = LocalBatchSource(
        [[]], schema=T.Schema.of(("v", T.INT64)))
    out = HashAggregateExec(
        [], [CountStar().alias("n"), Sum(col("v")).alias("s")], src
    ).collect()
    assert out.num_rows == 1
    assert out.column("n").to_pylist(1) == [0]
    assert out.column("s").to_pylist(1) == [None]


def test_partial_final_split(rng):
    """Two-phase aggregation as the distributed planner will wire it."""
    df = _sales_df(rng)
    partial = HashAggregateExec(
        [col("store")], [Sum(col("qty")).alias("s"),
                         Average(col("price")).alias("a")],
        LocalBatchSource.from_pandas(df, num_partitions=4),
        mode=AggMode.PARTIAL)
    # the exchange-to-one-partition the distributed planner will insert
    final = HashAggregateExec(
        [col("store")], [Sum(col("qty")).alias("s"),
                         Average(col("price")).alias("a")],
        CoalescePartitionsExec(1, partial), mode=AggMode.FINAL)
    out = final.to_pandas().sort_values("store").reset_index(drop=True)
    exp = (df.groupby("store").agg(s=("qty", "sum"), a=("price", "mean"))
           .reset_index())
    assert out["store"].tolist() == exp["store"].tolist()
    assert out["s"].tolist() == exp["s"].tolist()
    np.testing.assert_allclose(out["a"], exp["a"], rtol=1e-12)


def test_topn_nulls_last_with_sparse_mask_and_fewer_valid_than_k():
    """Regression (f32 prune): the nulls-last sentinel must not collapse
    into the masked-row -inf in the f32 candidate space — filtered-out
    rows at low indices must never displace null-key rows from top-N."""
    from spark_rapids_tpu.exec.basic import FilterExec
    # low-index rows all FILTERED OUT; 3 valid non-null rows < k=5;
    # null-key rows at high indices must fill the remaining slots.
    # 500 rows so capacity exceeds the K' candidate budget (~123) and
    # the pruned path actually engages.
    df = pd.DataFrame({
        "keep": [0] * 494 + [1] * 6,
        "x": [float(i) for i in range(494)] + [7.0, None, 3.0, None, 9.0,
                                               None],
    })
    plan = SortedTopNExec(
        5, [desc(col("x"))],
        FilterExec(col("keep") > lit(0), LocalBatchSource.from_pandas(df)))
    out = plan.to_pandas()
    vals = [None if pd.isna(v) else float(v) for v in out["x"]]
    assert vals == [9.0, 7.0, 3.0, None, None], vals


def test_verify_handles_flags_on_mixed_devices():
    """ADVICE r3: flags committed to different mesh devices must not
    break the single-stack readback (jnp.stack raises on mixed-device
    operands)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.utils.checks import (
        BatchCheck, FastPathInvalid, verify)
    devs = jax.devices("cpu")
    assert len(devs) >= 2
    flags = [jax.device_put(jnp.asarray(i == 2), devs[i % 2])
             for i in range(4)]
    checks = [BatchCheck(f, origin=f"c{i}") for i, f in enumerate(flags)]
    with pytest.raises(FastPathInvalid) as ei:
        verify(checks)
    assert [c.origin for c in ei.value.checks] == ["c2"]
    # all-clean across devices resolves silently
    verify([BatchCheck(jax.device_put(jnp.asarray(False), devs[i % 2]),
                       origin=f"ok{i}") for i in range(3)])


def test_variance_welford_large_magnitude(rng):
    """ADVICE r3: (sum, sum_sq) intermediates cancel catastrophically on
    large-magnitude low-variance data; the Welford (count, mean, m2)
    buffer must match pandas ddof=1 through BOTH the single-phase and
    the partial/final (merge) paths."""
    from spark_rapids_tpu.exprs.aggregates import StddevSamp, VarianceSamp
    df = pd.DataFrame({
        "g": rng.integers(0, 5, 400).astype(np.int64),
        # values ~1e8 with variance ~1: sum_sq ~1e16 per row, so the
        # old s2 - s^2/n path lost every significant digit
        "x": 1e8 + rng.normal(size=400),
    })
    exp = (df.groupby("g")["x"].agg(v="var", s="std").reset_index()
           .sort_values("g").reset_index(drop=True))
    single = HashAggregateExec(
        [col("g")],
        [VarianceSamp(col("x")).alias("v"), StddevSamp(col("x")).alias("s")],
        CoalescePartitionsExec(
            1, LocalBatchSource.from_pandas(df, num_partitions=3)))
    out = single.to_pandas().sort_values("g").reset_index(drop=True)
    np.testing.assert_allclose(out["v"], exp["v"], rtol=1e-6)
    np.testing.assert_allclose(out["s"], exp["s"], rtol=1e-6)
    partial = HashAggregateExec(
        [col("g")],
        [VarianceSamp(col("x")).alias("v"), StddevSamp(col("x")).alias("s")],
        LocalBatchSource.from_pandas(df, num_partitions=4),
        mode=AggMode.PARTIAL)
    final = HashAggregateExec(
        [col("g")],
        [VarianceSamp(col("x")).alias("v"), StddevSamp(col("x")).alias("s")],
        CoalescePartitionsExec(1, partial), mode=AggMode.FINAL)
    out2 = final.to_pandas().sort_values("g").reset_index(drop=True)
    np.testing.assert_allclose(out2["v"], exp["v"], rtol=1e-6)
    np.testing.assert_allclose(out2["s"], exp["s"], rtol=1e-6)
    # n<2 groups are null
    tiny = pd.DataFrame({"g": np.array([0, 1, 1], np.int64),
                         "x": np.array([5.0, 2.0, 4.0])})
    out3 = HashAggregateExec(
        [col("g")], [VarianceSamp(col("x")).alias("v")],
        CoalescePartitionsExec(
            1, LocalBatchSource.from_pandas(tiny))).to_pandas()
    out3 = out3.sort_values("g").reset_index(drop=True)
    assert pd.isna(out3["v"][0]) and abs(out3["v"][1] - 2.0) < 1e-12


def test_first_last(rng):
    b = ColumnarBatch.from_numpy(
        {"k": np.array([1, 1, 1, 2], np.int64),
         "v": np.array([0, 10, 20, 30], np.int64)},
        validity={"v": np.array([False, True, True, True])})
    out = HashAggregateExec(
        [col("k")],
        [First(col("v"), ignore_nulls=True).alias("f"),
         Last(col("v")).alias("l")],
        LocalBatchSource([[b]])).collect()
    rows = {k: (f, l) for k, f, l in zip(
        out.column("k").to_pylist(2), out.column("f").to_pylist(2),
        out.column("l").to_pylist(2))}
    assert rows[1] == (10, 20)
    assert rows[2] == (30, 30)


def test_coalesce_batches(rng):
    df = pd.DataFrame({"x": np.arange(100, dtype=np.int64)})
    src = LocalBatchSource.from_pandas(df, num_partitions=8)
    plan = CoalesceBatchesExec(TargetSize(1 << 20), src)
    batches = list(plan.execute_columnar())
    assert sum(b.num_rows for b in batches) == 100
    # 8 partitions stay separate (partition-local), each coalesced
    assert len(batches) == 8


def test_limits(rng):
    df = pd.DataFrame({"x": np.arange(100, dtype=np.int64)})
    src = LocalBatchSource.from_pandas(df, num_partitions=4)
    local = LocalLimitExec(10, src)
    total = sum(b.num_rows for it in local.execute_partitions()
                for b in it)
    assert total == 40  # 10 per partition
    glob = GlobalLimitExec(10, src)
    assert glob.collect().num_rows == 10


def test_top_n(rng):
    df = pd.DataFrame({"x": rng.permutation(1000).astype(np.int64)})
    plan = SortedTopNExec(5, [desc(col("x"))],
                          LocalBatchSource.from_pandas(df,
                                                       num_partitions=4))
    out = plan.collect()
    assert out.column("x").to_pylist(5) == [999, 998, 997, 996, 995]


def test_global_sort_across_partitions():
    df = pd.DataFrame({"x": np.array([5, 1, 9, 3, 7, 2, 8, 0], np.int64)})
    out = SortExec([asc(col("x"))],
                   LocalBatchSource.from_pandas(df, num_partitions=2)
                   ).to_pandas()
    assert out["x"].tolist() == [0, 1, 2, 3, 5, 7, 8, 9]


# -- dictionary fast path (conf-gated sort-free group-by) -------------------
from spark_rapids_tpu import config as C  # noqa: E402


def _dict_conf():
    return C.RapidsConf({
        "spark.rapids.tpu.dictGroupby.enabled": True,
        "spark.rapids.sql.variableFloatAgg.enabled": True})


def test_dict_groupby_parity_with_sort_path():
    """Same plan, conf on vs off: identical groups/counts, sums within
    f32-accumulation tolerance; nulls in keys AND values covered."""
    import pandas as pd
    from spark_rapids_tpu.exprs.aggregates import Average, Count, Sum
    from spark_rapids_tpu.plan import CpuAggregate, CpuSource, accelerate, collect
    rng = np.random.default_rng(8)
    n = 5000
    df = pd.DataFrame({
        "k": pd.array([None if i % 97 == 0 else int(rng.integers(10, 200))
                       for i in range(n)], "Int64"),
        "v": pd.array([None if i % 13 == 0 else float(rng.uniform(0, 50))
                       for i in range(n)], "Float64"),
    })
    src = CpuSource.from_pandas(df, num_partitions=2)
    plan = CpuAggregate([col("k")],
                        [Sum(col("v")).alias("sv"),
                         Count(col("v")).alias("cv"),
                         Count(None).alias("c"),
                         Average(col("v")).alias("av")], src)
    base_conf = C.RapidsConf(
        {"spark.rapids.sql.variableFloatAgg.enabled": True})
    expected = collect(accelerate(plan, base_conf), base_conf)
    got = collect(accelerate(plan, _dict_conf()), _dict_conf())
    e = expected.sort_values("k", ignore_index=True, na_position="first")
    g = got.sort_values("k", ignore_index=True, na_position="first")
    assert len(e) == len(g)
    np.testing.assert_array_equal(e["k"].isna(), g["k"].isna())
    np.testing.assert_array_equal(e["c"].to_numpy(), g["c"].to_numpy())
    np.testing.assert_array_equal(e["cv"].to_numpy(), g["cv"].to_numpy())
    np.testing.assert_allclose(e["sv"].astype(float),
                               g["sv"].astype(float), rtol=2e-3)
    np.testing.assert_allclose(e["av"].astype(float),
                               g["av"].astype(float), rtol=2e-3)


def test_dict_groupby_falls_back_on_wide_range():
    """Keys spanning more than maxGroups silently use the sort path."""
    import pandas as pd
    from spark_rapids_tpu.exprs.aggregates import Sum
    from spark_rapids_tpu.plan import CpuAggregate, CpuSource, accelerate, collect
    rng = np.random.default_rng(9)
    df = pd.DataFrame({
        "k": rng.integers(0, 1 << 40, 800).astype(np.int64),
        "v": rng.uniform(0, 1, 800)})
    src = CpuSource.from_pandas(df)
    plan = CpuAggregate([col("k")], [Sum(col("v")).alias("sv")], src)
    got = collect(accelerate(plan, _dict_conf()), _dict_conf())
    exp = df.groupby("k")["v"].sum()
    assert len(got) == len(exp)
    np.testing.assert_allclose(
        got.sort_values("k")["sv"].astype(float).to_numpy(),
        exp.sort_index().to_numpy(), rtol=1e-6)


def test_dict_groupby_falls_back_on_minmax():
    """Min/Max aggregates (not expressible as one-hot sums) fall back."""
    import pandas as pd
    from spark_rapids_tpu.exprs.aggregates import Min, Sum
    from spark_rapids_tpu.plan import CpuAggregate, CpuSource, accelerate, collect
    rng = np.random.default_rng(10)
    df = pd.DataFrame({
        "k": rng.integers(0, 50, 500).astype(np.int64),
        "v": rng.uniform(0, 1, 500)})
    src = CpuSource.from_pandas(df)
    plan = CpuAggregate([col("k")], [Min(col("v")).alias("mv"),
                                     Sum(col("v")).alias("sv")], src)
    got = collect(accelerate(plan, _dict_conf()), _dict_conf())
    exp = df.groupby("k").agg(mv=("v", "min"), sv=("v", "sum"))
    np.testing.assert_allclose(
        got.sort_values("k")["mv"].astype(float).to_numpy(),
        exp["mv"].to_numpy(), rtol=1e-6)


class TestDictFastPathDeopt:
    def test_overflow_excess_deopts_and_recovers(self):
        """First batch sizes a tiny key window; a later batch overflows
        past the inline budget -> the deferred excess check fires at the
        collect boundary, the fast path deopts, and the re-executed
        query returns exact results (utils/checks.py discipline)."""
        import numpy as np
        import pandas as pd
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.exec.aggregate import (AggMode,
                                                     HashAggregateExec)
        from spark_rapids_tpu.exec.basic import LocalBatchSource
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.exprs.aggregates import Count, Sum
        from spark_rapids_tpu.exprs.base import col

        rng = np.random.default_rng(11)
        k1 = rng.integers(0, 8, 512).astype(np.int64)
        # FLOAT32 values: a FLOAT64 measure never takes this lane
        v1 = rng.uniform(0, 10, 512).astype(np.float32)
        # batch 2: window anchored at its own kmin=0 with g_pad sized
        # from batch 1 (8 -> padded) — thousands of distinct overflow
        # keys blow the inline budget
        k2 = np.concatenate([rng.integers(0, 8, 100),
                             rng.integers(10_000, 90_000, 3000)]
                            ).astype(np.int64)
        v2 = rng.uniform(0, 10, 3100).astype(np.float32)
        b1 = ColumnarBatch.from_numpy({"k": k1, "v": v1})
        b2 = ColumnarBatch.from_numpy({"k": k2, "v": v2})
        src = LocalBatchSource([[b1, b2]])
        agg = HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s"),
                         Count(col("v")).alias("c")],
            src, mode=AggMode.COMPLETE)
        conf = C.RapidsConf(
            {"spark.rapids.sql.variableFloatAgg.enabled": True})
        with C.session(conf):
            got = agg.collect().to_pandas().sort_values(
                "k", ignore_index=True)
        df = pd.DataFrame({"k": np.concatenate([k1, k2]),
                           "v": np.concatenate([v1, v2])})
        exp = df.groupby("k").agg(s=("v", "sum"), c=("v", "size")
                                  ).reset_index()
        assert len(got) == len(exp)
        assert (got["c"].astype(int).to_numpy()
                == exp["c"].to_numpy()).all()
        np.testing.assert_allclose(got["s"].astype(float).to_numpy(),
                                   exp["s"].to_numpy(), rtol=2e-3)
        # the deopt disabled the fast path on this exec
        assert agg._dict_range_misses >= 3


# -- multi-key dictionary fast path ------------------------------------------
def _multi_key_frame(rng, n=20000, null_frac=0.01):
    import pandas as pd
    df = pd.DataFrame({
        "a": rng.integers(100, 137, n).astype(np.int64),
        "b": rng.integers(-5, 9, n).astype(np.int64),
        "c": rng.integers(0, 4, n).astype(np.int64),
        "v": rng.uniform(0, 10, n),
    })
    for col_ in ("a", "b"):
        idx = rng.choice(n, max(int(n * null_frac), 1), replace=False)
        df[col_] = df[col_].astype("Int64")
        df.loc[idx, col_] = pd.NA
    return df


def _run_agg_pair(df, keys, conf_extra=None):
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exprs.aggregates import Average, Count, Sum
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.plan import (CpuAggregate, CpuSource,
                                       accelerate, collect)
    src = CpuSource.from_pandas(df, num_partitions=2)
    plan = CpuAggregate(
        [col(k) for k in keys],
        [Sum(col("v")).alias("sv"), Count(col("v")).alias("cnt"),
         Average(col("v")).alias("av")], src)
    conf = C.RapidsConf(dict(
        {"spark.rapids.sql.variableFloatAgg.enabled": True},
        **(conf_extra or {})))
    got = collect(accelerate(plan, conf), conf)
    exp = plan.collect()
    from parity import compare_frames
    compare_frames(exp, got, f"multikey-{keys}", rtol=5e-3)


def test_dict_groupby_two_integral_keys_with_nulls():
    rng = np.random.default_rng(31)
    _run_agg_pair(_multi_key_frame(rng), ["a", "b"])


def test_dict_groupby_three_integral_keys():
    rng = np.random.default_rng(32)
    _run_agg_pair(_multi_key_frame(rng, null_frac=0.0),
                  ["a", "b", "c"])


def test_dict_groupby_multi_key_budget_overflow_falls_back():
    # product of spans blows the budget: the plan must fall back to the
    # sort lane and still be correct
    rng = np.random.default_rng(33)
    import pandas as pd
    n = 8000
    df = pd.DataFrame({
        "a": rng.integers(0, 100000, n).astype(np.int64),
        "b": rng.integers(0, 100000, n).astype(np.int64),
        "v": rng.uniform(0, 10, n),
    })
    _run_agg_pair(df, ["a", "b"])


def test_sort_lane_compaction_deopt_on_many_groups(rng):
    """Checked group-batch compaction: a sort-lane partial compacts to
    COMPACT_GROUPS_CAP optimistically; when the true group count
    overflows it, the deferred check must deopt (escalate the cap +
    retry) and the final result must still be exact."""
    from spark_rapids_tpu import config as C
    n = 1 << 16
    n_groups = (1 << 14) + 500     # overflows the 16K compaction target
    df = pd.DataFrame({
        "k": rng.permutation(np.arange(n, dtype=np.int64) % n_groups),
        "v": rng.uniform(0, 10, n),
    })
    conf = C.RapidsConf({"spark.rapids.tpu.dictGroupby.enabled": False})
    with C.session(conf):
        plan = HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s"),
                         Count(col("v")).alias("c")],
            LocalBatchSource.from_pandas(df))
        assert getattr(plan, "_compact_cap", None) is None
        out = plan.to_pandas().sort_values("k", ignore_index=True)
        # the deopt must have fired (groups > 16K target) and escalated
        # the learned cap exactly one tier
        assert plan._compact_cap == HashAggregateExec.COMPACT_GROUPS_CAP * 4
    exp = (df.groupby("k").agg(s=("v", "sum"), c=("v", "size"))
           .reset_index())
    assert len(out) == n_groups
    np.testing.assert_allclose(out["s"].astype(float), exp["s"],
                               rtol=1e-9)
    assert (out["c"].astype(int).to_numpy() == exp["c"].to_numpy()).all()


def test_sort_lane_compaction_keeps_small_group_counts_exact(rng):
    """Compaction fast path (group count under the target): results must
    be exact and the fast path must stay enabled."""
    from spark_rapids_tpu import config as C
    n = 1 << 16
    df = pd.DataFrame({
        "k": rng.integers(0, 300, n).astype(np.int64),
        "v": rng.uniform(0, 10, n),
    })
    conf = C.RapidsConf({"spark.rapids.tpu.dictGroupby.enabled": False})
    with C.session(conf):
        plan = HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s")],
            LocalBatchSource.from_pandas(df))
        out = plan.to_pandas().sort_values("k", ignore_index=True)
        assert not getattr(plan, "_compact_disabled", False)
    exp = df.groupby("k").agg(s=("v", "sum")).reset_index()
    np.testing.assert_allclose(out["s"].astype(float), exp["s"], rtol=1e-9)


def test_groupby_negative_zero_f32_one_group():
    """-0.0 and 0.0 form ONE SQL group (word-equality boundaries must
    normalize the f32 bit encode like murmur3 does)."""
    b = ColumnarBatch.from_numpy(
        {"k": np.array([-0.0, 0.0, 1.0, -0.0], np.float32),
         "v": np.array([1, 2, 4, 8], np.int64)})
    out = HashAggregateExec(
        [col("k")], [Sum(col("v")).alias("s")],
        LocalBatchSource([[b]])).to_pandas()
    got = {float(k): int(s) for k, s in zip(out["k"], out["s"])}
    assert got == {0.0: 11, 1.0: 4}


def test_compaction_escalation_ladder_resolves_in_one_collect(rng):
    """A group count past 4x the compaction cap resolves WITHIN one
    collect: bounded deopt retries climb the x4 escalation ladder
    (16K -> 64K -> 256K) instead of jumping to full-width kernels
    (whose compile-time buffer assignment OOMed HBM at 8M-row caps),
    and later collects of the SAME plan start at the learned cap with
    no further deopts."""
    from spark_rapids_tpu import config as C
    n = 1 << 17
    n_groups = (1 << 16) + 123     # > 4x the 16K target
    df = pd.DataFrame({
        "k": rng.permutation(np.arange(n, dtype=np.int64) % n_groups),
        "v": rng.uniform(0, 10, n),
    })
    conf = C.RapidsConf({"spark.rapids.tpu.dictGroupby.enabled": False})
    with C.session(conf):
        plan = HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s")],
            LocalBatchSource.from_pandas(df))
        out = plan.to_pandas()
        assert len(out) == n_groups
        # the ladder climbed twice within the first collect
        assert plan._compact_cap == \
            HashAggregateExec.COMPACT_GROUPS_CAP * 16
        # second collect: the learned cap fits, no further escalation
        out2 = plan.to_pandas()
        assert len(out2) == n_groups
        assert plan._compact_cap == \
            HashAggregateExec.COMPACT_GROUPS_CAP * 16
    exp = df.groupby("k")["v"].sum().reset_index().sort_values(
        "k", ignore_index=True)
    got = out.sort_values("k", ignore_index=True)
    np.testing.assert_allclose(got["s"].astype(float), exp["v"], rtol=1e-9)


# -- hash-grouping lane (wide key sets route via murmur3 grouping) ----------

def _wide_key_df(rng, n=400):
    """5 group keys incl. strings: estimate_packed_words > 4 so the
    hash-grouping lane engages."""
    return pd.DataFrame({
        "city": rng.choice(["springfield", "shelbyville", "ogdenville",
                            "capital city"], n),
        "street": rng.choice(["elm st", "oak ave", "main st"], n),
        "zip": rng.choice(["12345", "67890"], n),
        "yr": rng.integers(1999, 2002, n).astype(np.int64),
        "sku": rng.integers(0, 5, n).astype(np.int64),
        "v": rng.uniform(0, 10, n),
    })


def test_hash_grouping_lane_parity(rng):
    df = _wide_key_df(rng)
    keys = ["city", "street", "zip", "yr", "sku"]
    plan = HashAggregateExec(
        [col(k) for k in keys],
        [Sum(col("v")).alias("s"), Count(col("v")).alias("c")],
        LocalBatchSource.from_pandas(df))
    assert plan._use_hash_grouping(
        ColumnarBatch.from_pandas(df)), "lane must engage for wide keys"
    got = plan.to_pandas().sort_values(keys, ignore_index=True)
    exp = (df.groupby(keys).agg(s=("v", "sum"), c=("v", "size"))
           .reset_index().sort_values(keys, ignore_index=True))
    np.testing.assert_allclose(got["s"].astype(float), exp["s"], rtol=1e-9)
    np.testing.assert_array_equal(got["c"].astype(int), exp["c"])


def test_hash_grouping_shifted_null_patterns(rng):
    """(NULL, x, ...) vs (x, NULL, ...) keys: Spark's null-keeps-seed
    murmur3 chaining hashes these EQUAL on every seed, which would
    fire the collision deopt systematically; the grouping hash mixes a
    per-column null marker so these group correctly on the fast lane."""
    n = 64
    a = np.arange(n).astype(np.float64)
    b = np.arange(n).astype(np.float64)
    a[::2] = np.nan   # -> nulls via from_pandas
    b[1::2] = np.nan
    df = pd.DataFrame({
        "a": a, "b": b,
        "s1": ["x"] * n, "s2": ["y"] * n, "s3": ["z"] * n,
        "v": np.ones(n),
    })
    keys = ["a", "b", "s1", "s2", "s3"]
    plan = HashAggregateExec(
        [col(k) for k in keys], [Sum(col("v")).alias("s")],
        LocalBatchSource.from_pandas(df))
    assert plan._use_hash_grouping(ColumnarBatch.from_pandas(df))
    got = plan.to_pandas()
    exp = (df.groupby(keys, dropna=False).agg(s=("v", "sum"))
           .reset_index())
    assert len(got) == len(exp)
    # the lane must NOT have deopted (no collision on ordinary nulls)
    assert not getattr(plan, "_hash_group_disabled", False)
    np.testing.assert_allclose(
        got.sort_values(keys, ignore_index=True)["s"].astype(float),
        exp.sort_values(keys, ignore_index=True)["s"], rtol=1e-9)


def test_hash_grouping_narrow_keys_stay_lexicographic(rng):
    df = _sales_df(rng)
    plan = HashAggregateExec(
        [col("sku")], [Sum(col("qty")).alias("s")],
        LocalBatchSource.from_pandas(df))
    assert not plan._use_hash_grouping(ColumnarBatch.from_pandas(df))


def test_dict_groupby_integral_sum_exact(rng):
    """Sum over INT columns rides the dict lane with the f32-exactness
    certificate (no variableFloatAgg needed) and matches pandas
    bit-exactly."""
    from spark_rapids_tpu import config as C
    n = 1 << 14
    df = pd.DataFrame({
        "k": rng.integers(0, 200, n).astype(np.int64),
        "v": rng.integers(-100, 100, n).astype(np.int64),
    })
    with C.session(C.RapidsConf({})):
        plan = HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s"),
                         Count(col("v")).alias("c")],
            LocalBatchSource.from_pandas(df))
        assert plan._dict_qual is not None, "int Sum must qualify"
        out = plan.to_pandas().sort_values("k", ignore_index=True)
    exp = (df.groupby("k").agg(s=("v", "sum"), c=("v", "size"))
           .reset_index())
    np.testing.assert_array_equal(out["s"].astype(np.int64), exp["s"])
    np.testing.assert_array_equal(out["c"].astype(np.int64), exp["c"])


def test_dict_groupby_integral_sum_overflow_deopts(rng):
    """Group sums past the f32-exact range must deopt to the sort lane
    and still return exact results."""
    from spark_rapids_tpu import config as C
    n = 1 << 13
    df = pd.DataFrame({
        "k": rng.integers(0, 4, n).astype(np.int64),
        "v": rng.integers(1 << 22, 1 << 26, n).astype(np.int64),
    })
    with C.session(C.RapidsConf({})):
        plan = HashAggregateExec(
            [col("k")], [Sum(col("v")).alias("s")],
            LocalBatchSource.from_pandas(df))
        out = plan.to_pandas().sort_values("k", ignore_index=True)
        # the inexactness certificate must have fired
        assert plan._dict_range_misses >= 1 << 20, "expected deopt"
    exp = df.groupby("k").agg(s=("v", "sum")).reset_index()
    np.testing.assert_array_equal(out["s"].astype(np.int64), exp["s"])
