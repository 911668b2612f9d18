"""Shim layer tests (reference `shims/` + `ShimLoader.scala`): version
resolution, Databricks sniffing, per-version behavior drift, and the
spark310 accelerated columnar→row transition parity."""
import importlib

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import shims as S
from spark_rapids_tpu.exec.basic import LocalBatchSource
from spark_rapids_tpu.plan import CpuSource, accelerate, collect
from spark_rapids_tpu.plan.transitions import (AcceleratedColumnarToRowExec,
                                               ColumnarToRowExec)
from spark_rapids_tpu.shuffle.manager import (MapOutputRegistry, MapStatus,
                                              TpuShuffleManager)


def conf(**kv):
    return C.RapidsConf({k.replace("__", "."): v for k, v in kv.items()})


# -- loader -----------------------------------------------------------------
def test_loader_resolves_every_supported_version():
    for provider in S.ALL_SHIMS:
        for name in provider.VERSION_NAMES:
            assert type(S.get_spark_shims(name)) is provider


def test_loader_unknown_version_raises():
    with pytest.raises(RuntimeError, match="3.2.0"):
        S.get_spark_shims("3.2.0")


def test_loader_caches_instances():
    assert S.get_spark_shims("3.0.1") is S.get_spark_shims("3.0.1")


def test_databricks_detection_from_cluster_tag():
    c = conf(**{"spark.databricks.clusterUsageTags.clusterId": "0001-x",
                "spark.rapids.tpu.sparkVersion": "3.0.0"})
    assert S.detect_version(c) == "3.0.0-databricks"
    assert isinstance(S.current_shims(c), S.Spark300dbShims)


def test_default_version_is_301():
    assert isinstance(S.current_shims(conf()), S.Spark301Shims)


def test_databricks_without_db_shim_degrades_to_upstream():
    """A Databricks cluster tag on a base version with no -databricks
    provider must not break plan rewrites."""
    c = conf(**{"spark.databricks.clusterUsageTags.clusterId": "0001-x",
                "spark.rapids.tpu.sparkVersion": "3.0.1"})
    assert S.detect_version(c) == "3.0.1"
    assert isinstance(S.current_shims(c), S.Spark301Shims)


def test_shim_version_parse_and_order():
    v = S.ShimVersion.parse("3.1.1-SNAPSHOT")
    assert (v.major, v.minor, v.patch) == (3, 1, 1)
    assert S.ShimVersion.parse("3.0.0") < S.ShimVersion.parse("3.1.0")
    assert S.ShimVersion.parse("3.0.0-databricks").databricks


def test_register_external_provider():
    class CustomShims(S.Spark301Shims):
        VERSION_NAMES = ("3.0.1-custom",)
    S.register_provider(CustomShims)
    assert isinstance(S.get_spark_shims("3.0.1-custom"), CustomShims)


# -- per-version drift ------------------------------------------------------
def test_shuffle_manager_classes_resolve_per_version():
    for version, pkg in [("3.0.0", "spark300"), ("3.0.1", "spark301"),
                         ("3.0.2", "spark302"), ("3.1.0", "spark310"),
                         ("3.0.0-databricks", "spark300db")]:
        path = S.get_spark_shims(version).shuffle_manager_class()
        mod, cls_name = path.rsplit(".", 1)
        assert pkg in mod
        cls = getattr(importlib.import_module(mod), cls_name)
        assert issubclass(cls, TpuShuffleManager)


def test_aqe_reader_name_databricks_fork():
    assert S.get_spark_shims("3.0.0").aqe_shuffle_reader_name() \
        == "CustomShuffleReaderExec"
    assert S.get_spark_shims("3.0.0-databricks").aqe_shuffle_reader_name() \
        == "DatabricksShuffleReaderExec"


def test_map_index_ranges_gate():
    MapOutputRegistry.clear()
    sid = 991
    for map_id, sizes in enumerate([[10, 0, 5], [0, 7, 3]]):
        MapOutputRegistry.register(
            sid, map_id, MapStatus("e0", "local", sizes))
    s310 = S.get_spark_shims("3.1.0")
    got = s310.get_map_sizes(MapOutputRegistry, sid, 1, 2, 0, 3)
    assert got == [(1, 1, 7), (1, 2, 3)]
    # full range works everywhere
    s300 = S.get_spark_shims("3.0.0")
    full = s300.get_map_sizes(MapOutputRegistry, sid, 0, None, 0, 3)
    assert (0, 0, 10) in full and (1, 1, 7) in full
    with pytest.raises(NotImplementedError):
        s300.get_map_sizes(MapOutputRegistry, sid, 1, 2, 0, 3)
    MapOutputRegistry.clear()


def test_file_partition_packing():
    files = [("a", 10), ("b", 200), ("c", 30), ("d", 5)]
    parts = S.get_spark_shims("3.0.1").make_file_partitions(
        files, max_bytes=256, open_cost=8)
    assert sorted(f for p in parts for f, _ in p) == ["a", "b", "c", "d"]
    for p in parts:
        assert sum(sz + 8 for _, sz in p) <= 256 or len(p) == 1


def test_first_last_construction():
    from spark_rapids_tpu.exprs.aggregates import First, Last
    from spark_rapids_tpu.exprs.base import col
    sh = S.get_spark_shims("3.0.0")
    f = sh.make_first_last(col("a"), last=False, ignore_nulls=True)
    l = sh.make_first_last(col("a"), last=True, ignore_nulls=False)
    assert isinstance(f, First) and f.ignore_nulls
    assert isinstance(l, Last) and not l.ignore_nulls


# -- accelerated transition -------------------------------------------------
def _df():
    return pd.DataFrame({
        "a": np.arange(20, dtype=np.int64),
        "b": [float(i) if i % 3 else np.nan for i in range(20)],
        "s": [None if i % 5 == 0 else f"v{i}" for i in range(20)],
    })


def test_transition_classes_per_version():
    src = LocalBatchSource.from_pandas(_df())
    assert type(S.get_spark_shims("3.0.1")
                .columnar_to_row_transition(src)) is ColumnarToRowExec
    assert type(S.get_spark_shims("3.1.0")
                .columnar_to_row_transition(src)) \
        is AcceleratedColumnarToRowExec


def test_accelerated_transition_parity():
    df = _df()
    src = LocalBatchSource.from_pandas(df, num_partitions=2)
    base = ColumnarToRowExec(src).collect()
    fast = AcceleratedColumnarToRowExec(src).collect()
    pd.testing.assert_frame_equal(base, fast)


def _find_node(plan, cls):
    found = []

    def walk(n):
        if isinstance(n, cls):
            found.append(n)
        kids = getattr(n, "children", [])
        for k in kids:
            walk(k)
        tk = getattr(n, "tpu_child", None)
        if tk is not None:
            walk(tk)
    walk(plan)
    return found


def test_accelerated_transition_in_plan_rewrite():
    """With sparkVersion=3.1.0 a CPU-fallback boundary below a TPU
    island gets the accelerated transition end-to-end."""
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.plan import CpuFilter, CpuProject
    df = _df()
    build = lambda: CpuFilter(
        col("a") > 4, CpuProject([col("a"), col("b"), col("s")],
                                 CpuSource.from_pandas(df)))
    expected = build().collect()
    c = conf(**{"spark.rapids.tpu.sparkVersion": "3.1.0",
                "spark.rapids.sql.exec.CpuFilter": False})
    out = accelerate(build(), c)
    assert _find_node(out, AcceleratedColumnarToRowExec), \
        "expected the spark310 accelerated transition in the plan"
    got = collect(out, c)
    assert list(got.columns) == list(expected.columns)
    for name in expected.columns:
        e, g = expected[name], got[name]
        np.testing.assert_array_equal(e.isna().to_numpy(),
                                      g.isna().to_numpy())
        ev, gv = e[~e.isna()].tolist(), g[~g.isna()].tolist()
        assert ev == gv, f"column {name}"


# -- round-2 drift points (reference SparkShims.scala:57-136) ---------------
def test_shuffle_exchange_constructor_drift():
    """3.0 exchanges always allow AQE coalescing; 3.1's
    ShuffleExchangeLike carries canChangeNumPartitions."""
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.shims.versions import (Spark300Shims,
                                                 Spark310Shims)
    from spark_rapids_tpu.shuffle.partitioning import RoundRobinPartitioning
    import pandas as pd
    src = LocalBatchSource.from_pandas(pd.DataFrame({"a": [1, 2]}))
    part = RoundRobinPartitioning(2)
    ex300 = Spark300Shims().make_shuffle_exchange(
        part, src, can_change_num_partitions=False)
    assert ex300.can_change_num_partitions is True  # 3.0: no such flag
    ex310 = Spark310Shims().make_shuffle_exchange(
        part, src, can_change_num_partitions=False)
    assert ex310.can_change_num_partitions is False


def test_build_side_and_nested_loop_constructor():
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exec.joins import JoinType, NestedLoopJoinExec
    from spark_rapids_tpu.shims.versions import ALL_SHIMS
    import pandas as pd
    l = LocalBatchSource.from_pandas(pd.DataFrame({"a": [1]}))
    r = LocalBatchSource.from_pandas(pd.DataFrame({"b": [2]}))
    for cls in ALL_SHIMS:
        s = cls()
        # the mapping is version-stable; the DRIFT the shim hides is
        # where BuildSide lives (moved packages in 3.1)
        assert s.build_side_of(JoinType.LEFT_SEMI, "left") == "right"
        assert s.build_side_of(JoinType.INNER, "left") == "left"
        j = s.make_nested_loop_join(JoinType.CROSS, l, r, None,
                                    target_size_bytes=1024)
        assert isinstance(j, NestedLoopJoinExec)
        assert j.target_size_bytes == 1024


def test_databricks_prep_rule_injection_drift():
    """The built rule carries the Databricks fork's name only on the db
    shim — resolved from the PER-SESSION conf at build time, matching
    the plugin's deferred builder."""
    from spark_rapids_tpu.shims.versions import (Spark300dbShims,
                                                 Spark301Shims)
    for shim, expect_db in ((Spark301Shims(), False),
                            (Spark300dbShims(), True)):
        rule = shim.make_query_stage_prep_rule(
            C.RapidsConf(), lambda conf: (lambda plan: plan))
        name = getattr(rule, "__name__", "")
        assert (name == "DatabricksQueryStagePrepRule") == expect_db
        assert rule("PLAN") == "PLAN"  # still delegates to the rule


def test_databricks_file_partitions_pack_whole_files():
    """getPartitionSplitFiles drift: Databricks packs whole files."""
    from spark_rapids_tpu.io.scan import FileSplit
    from spark_rapids_tpu.shims.versions import (Spark300dbShims,
                                                 Spark301Shims)
    files = [FileSplit(path=f"/f{i}", start=0, length=10_000_000,
                       file_size=10_000_000) for i in range(3)]
    upstream = Spark301Shims().plan_file_partitions(
        files, max_bytes=4_000_000, open_cost=10_000, min_partitions=1)
    db = Spark300dbShims().plan_file_partitions(
        files, max_bytes=4_000_000, open_cost=10_000, min_partitions=1)
    up_splits = [s for p in upstream for s in p.splits]
    db_splits = [s for p in db for s in p.splits]
    assert any(s.length < 10_000_000 for s in up_splits)  # ranges
    assert all(s.length == 10_000_000 for s in db_splits)  # whole files


def test_copy_scan_with_small_file_opt(tmp_path):
    import pandas as pd
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.io.exec import ScanDescription, \
        TpuFileSourceScanExec
    from spark_rapids_tpu.shims import current_shims
    pd.DataFrame({"a": [1, 2, 3]}).to_parquet(tmp_path / "x.parquet")
    sd = ScanDescription(str(tmp_path), "parquet",
                         conf=C.get_active_conf())
    exec_ = TpuFileSourceScanExec(sd)
    for enabled in (True, False):
        copied = current_shims(C.get_active_conf()) \
            .copy_scan_with_small_file_opt(exec_, enabled)
        assert copied.scan.small_file_opt is enabled
        assert copied.scan is not exec_.scan
        out = copied.collect()
        assert out.num_rows == 3
    # behavior: with the opt off, each split reads through its OWN
    # reader (no cross-file coalescing) — two files -> >= 2 batches
    pd.DataFrame({"a": [4, 5]}).to_parquet(tmp_path / "y.parquet")
    sd2 = ScanDescription(str(tmp_path), "parquet",
                          conf=C.get_active_conf())
    base2 = TpuFileSourceScanExec(sd2)
    off = current_shims(C.get_active_conf()) \
        .copy_scan_with_small_file_opt(base2, False)
    batches = [b for it in off.execute_partitions() for b in it]
    assert sum(b.num_rows for b in batches) == 5
    assert len(batches) >= 2



def test_aqe_respects_pinned_partition_count():
    """3.1 contract end-to-end: a user repartition(N) planned under the
    3.1 shim is NOT coalesced by AQE; under 3.0 shims it may be."""
    from spark_rapids_tpu.plan import (CpuShuffleExchange, CpuSource,
                                       PartitioningSpec, accelerate,
                                       collect, ExecutionPlanCapture)
    from spark_rapids_tpu.exprs.base import col
    df = pd.DataFrame({"a": np.arange(64, dtype=np.int64)})
    plan = CpuShuffleExchange(
        PartitioningSpec("hash", 8, (col("a"),)),
        CpuSource.from_pandas(df, num_partitions=2))
    for ver, may_coalesce in (("3.0.1", True), ("3.1.0", False)):
        conf = C.RapidsConf({
            "spark.rapids.tpu.sparkVersion": ver,
            "spark.sql.adaptive.enabled": True,
            "spark.sql.adaptive.coalescePartitions.enabled": True})
        out = collect(accelerate(plan, conf), conf)
        assert sorted(out["a"]) == list(range(64))
        final = ExecutionPlanCapture.last_plan
        names = []

        def walk(n):
            names.append(type(n).__name__)
            for c in getattr(n, "children", []):
                walk(c)
        walk(final)
        coalesced = "CustomShuffleReaderExec" in names
        if not may_coalesce:
            assert not coalesced, f"{ver} must pin the partition count"


def test_unknown_version_fails_with_supported_list():
    """A NEW Spark version arriving has defined behavior: an exact-match
    miss fails loudly like the reference ShimLoader, naming the
    supported versions and the escape hatch."""
    import pytest
    from spark_rapids_tpu.shims.loader import get_spark_shims
    with pytest.raises(RuntimeError) as ei:
        get_spark_shims("3.0.9", conf=C.RapidsConf())
    msg = str(ei.value)
    assert "3.0.9" in msg and "3.0.2" in msg
    assert "allowUnknownSparkVersion" in msg


def test_unknown_version_conf_gated_nearest_minor_fallback():
    """With spark.rapids.tpu.allowUnknownSparkVersion, an unknown patch
    release falls back to the highest known shim of the same minor
    line (3.0.9 -> 3.0.2), with Databricks versions never
    cross-matching."""
    from spark_rapids_tpu.shims.loader import get_spark_shims
    conf = C.RapidsConf(
        {"spark.rapids.tpu.allowUnknownSparkVersion": True})
    shims = get_spark_shims("3.0.9", conf=conf)
    assert "3.0.2" in type(shims).VERSION_NAMES
    # a whole unknown minor line still fails (nothing near to pick)
    import pytest
    with pytest.raises(RuntimeError):
        get_spark_shims("9.9.0", conf=conf)


def test_unknown_version_fallback_not_leaked_across_sessions():
    """A fallback resolution cached by a gated session must NOT leak to
    a later session with the gate unset — that session still gets the
    documented RuntimeError (cache keyed per gate)."""
    import pytest
    from spark_rapids_tpu.shims.loader import get_spark_shims
    gated = C.RapidsConf(
        {"spark.rapids.tpu.allowUnknownSparkVersion": True})
    shims = get_spark_shims("3.0.8", conf=gated)
    assert "3.0.2" in type(shims).VERSION_NAMES
    with pytest.raises(RuntimeError):
        get_spark_shims("3.0.8", conf=C.RapidsConf())
    # the gated session still hits its cache
    assert get_spark_shims("3.0.8", conf=gated) is shims


def test_unknown_version_hint_only_when_actionable():
    """The error hint suggests the escape hatch only when it would
    actually help (a same-minor candidate exists and the gate is
    unset)."""
    import pytest
    from spark_rapids_tpu.shims.loader import get_spark_shims
    with pytest.raises(RuntimeError) as e1:
        get_spark_shims("9.9.0", conf=C.RapidsConf())
    assert "allowUnknownSparkVersion" not in str(e1.value)
    gated = C.RapidsConf(
        {"spark.rapids.tpu.allowUnknownSparkVersion": True})
    with pytest.raises(RuntimeError) as e2:
        get_spark_shims("9.9.1", conf=gated)
    assert "allowUnknownSparkVersion" not in str(e2.value)
