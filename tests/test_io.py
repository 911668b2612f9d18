"""I/O layer tests (reference: parquet/orc/csv read+write integration
tests, SURVEY.md §4 tier 3; unit tests of split planning and pushdown)."""
import datetime
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import io as tio
from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs.base import col, lit
from spark_rapids_tpu.io import pushdown as PD
from spark_rapids_tpu.io.csv import CsvOptions
from spark_rapids_tpu.io.exec import ScanDescription, TpuFileSourceScanExec
from spark_rapids_tpu.io.scan import (
    FileSplit, discover_files, plan_file_partitions)
from spark_rapids_tpu.io.writer import write_batches
from spark_rapids_tpu.plan import (
    CpuFilter, CpuProject, ExecutionPlanCapture, accelerate, collect)

def conf(**kv):
    return C.RapidsConf({k.replace("__", "."): v for k, v in kv.items()})


def compare(cpu_plan, c=None, sort_by=None):
    """Golden rule: run the plan on CPU only, then accelerated, diff."""
    expected = cpu_plan.collect()
    plan = accelerate(cpu_plan, c or conf())
    got = collect(plan)
    if sort_by:
        expected = expected.sort_values(sort_by, ignore_index=True)
        got = got.sort_values(sort_by, ignore_index=True)
    assert list(expected.columns) == list(got.columns)
    for name in expected.columns:
        e, g = expected[name], got[name]
        ena, gna = e.isna().to_numpy(), g.isna().to_numpy()
        np.testing.assert_array_equal(ena, gna, err_msg=f"null mask {name}")
        ev, gv = e[~ena].to_numpy(), g[~gna].to_numpy()
        if e.dtype == object or g.dtype == object:
            assert list(ev) == list(gv), f"column {name}"
        else:
            np.testing.assert_allclose(
                np.asarray(ev, float), np.asarray(gv, float), rtol=1e-6,
                err_msg=f"column {name}")
    return plan


def _sample_df(n=100, seed=7):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "i": np.arange(n, dtype=np.int64),
        "f": rng.normal(size=n),
        "s": [None if i % 11 == 0 else f"row{i}" for i in range(n)],
        "d": [datetime.date(2020, 1, 1) + datetime.timedelta(days=int(i))
              for i in range(n)],
    })


@pytest.fixture
def pq_path(tmp_path):
    df = _sample_df()
    p = tmp_path / "data.parquet"
    pq.write_table(pa.Table.from_pandas(df), p, row_group_size=20)
    return str(p)


# -- split planning ---------------------------------------------------------
def test_plan_file_partitions_packs_and_splits():
    files = [FileSplit(f"/f{i}", 0, 100 * 2 ** 20, 100 * 2 ** 20)
             for i in range(4)]
    parts = plan_file_partitions(files, 128 * 2 ** 20, 4 * 2 ** 20)
    total = sum(s.length for p in parts for s in p.splits)
    assert total == 4 * 100 * 2 ** 20
    for p in parts:
        assert sum(s.length + 4 * 2 ** 20 for s in p.splits) <= 128 * 2 ** 20

    big = [FileSplit("/big", 0, 300 * 2 ** 20, 300 * 2 ** 20)]
    parts = plan_file_partitions(big, 128 * 2 ** 20, 4 * 2 ** 20)
    assert len(parts) >= 3  # file was split
    covered = sorted((s.start, s.length) for p in parts for s in p.splits)
    end = 0
    for start, length in covered:
        assert start == end
        end = start + length
    assert end == 300 * 2 ** 20


def test_discover_hive_partitions(tmp_path):
    for year, n in ((2020, 3), (2021, 4)):
        d = tmp_path / f"year={year}"
        d.mkdir()
        pq.write_table(pa.Table.from_pandas(
            pd.DataFrame({"x": np.arange(n, dtype=np.int64)})),
            d / "part-0.parquet")
    files, part_schema = discover_files(str(tmp_path), ".parquet")
    assert len(files) == 2
    assert part_schema.names == ("year",)
    assert part_schema.field("year").dtype == T.INT64
    assert dict(files[0].partition_values)["year"] == 2020


# -- pushdown ---------------------------------------------------------------
def test_pushdown_range_pruning():
    stats = {"a": PD.ColumnStats(min=10, max=20, null_count=0,
                                 num_values=100)}
    assert PD.might_match(col("a") > 25, stats) is False
    assert PD.might_match(col("a") > 15, stats) is True
    assert PD.might_match(col("a") < 10, stats) is False
    assert PD.might_match(col("a") <= 10, stats) is True
    assert PD.might_match(col("a").eq(5), stats) is False
    assert PD.might_match(lit(25) > col("a"), stats) is True
    assert PD.might_match(lit(5) > col("a"), stats) is False
    # and/or composition
    assert PD.might_match((col("a") > 25) & (col("a") < 30), stats) is False
    assert PD.might_match((col("a") > 25) | (col("a") < 12), stats) is True


def test_pushdown_nulls_and_unknown():
    stats = {"a": PD.ColumnStats(min=1, max=2, null_count=100,
                                 num_values=100)}
    from spark_rapids_tpu.exprs.predicates import IsNotNull, IsNull
    assert PD.might_match(IsNotNull(col("a")), stats) is False
    assert PD.might_match(IsNull(col("a")), stats) is True
    assert PD.might_match(col("a") > 0, stats) is False  # all null
    # unknown column stays
    assert PD.might_match(col("zz") > 0, stats) is True


# -- parquet ----------------------------------------------------------------
def test_parquet_scan_parity(pq_path):
    scan = tio.read_parquet(pq_path)
    plan = compare(scan)
    assert isinstance(plan, TpuFileSourceScanExec)


def test_parquet_filter_pushdown_prunes_row_groups(pq_path):
    c = conf()
    scan = ScanDescription(pq_path, "parquet", conf=c)
    exec_ = TpuFileSourceScanExec(scan, pushed_filter=(col("i") >= 90), conf=c)
    rows = sum(b.num_rows for b in exec_.execute_columnar())
    # only the last row group (rows 80..99) survives the stats filter
    assert rows == 20


def test_parquet_legacy_rebase_falls_back(pq_path):
    """LEGACY hybrid-calendar rebase keeps the scan on CPU (reference
    GpuParquetScan.scala:1108-1115), via the version-variant conf key."""
    from spark_rapids_tpu.plan.overrides import accelerate
    from spark_rapids_tpu.plan.nodes import CpuNode
    key = "spark.sql.legacy.parquet.datetimeRebaseModeInRead"
    c = conf(**{key: "LEGACY"})
    out = accelerate(tio.read_parquet(pq_path), c)
    assert isinstance(out, CpuNode)
    ExecutionPlanCapture.assert_did_fall_back("CpuFileScan[parquet]")
    # 3.0.0 sessions use the boolean-era key
    c300 = conf(**{"spark.rapids.tpu.sparkVersion": "3.0.0",
                   "spark.sql.legacy.parquet.rebaseDateTimeInRead": "true"})
    out300 = accelerate(tio.read_parquet(pq_path), c300)
    assert isinstance(out300, CpuNode)


def test_parquet_filter_query_parity(pq_path):
    plan = CpuFilter((col("i") >= lit(25)) & (col("i") < lit(35)),
                     tio.read_parquet(pq_path))
    compare(plan)
    tpu_plan = ExecutionPlanCapture.last_plan
    scans = _find_scans(tpu_plan)
    assert scans and scans[0].pushed_filter is not None


def _find_scans(plan):
    out = []
    if isinstance(plan, TpuFileSourceScanExec):
        out.append(plan)
    for c in getattr(plan, "children", []):
        out.extend(_find_scans(c))
    return out


def test_parquet_partitioned_dataset(tmp_path):
    for year in (2020, 2021):
        d = tmp_path / f"year={year}"
        d.mkdir()
        pq.write_table(pa.Table.from_pandas(pd.DataFrame({
            "x": np.arange(5, dtype=np.int64) + year})), d / "p.parquet")
    scan = tio.read_parquet(str(tmp_path))
    assert scan.output_schema().names == ("x", "year")
    compare(scan, sort_by=["year", "x"])


def test_parquet_schema_evolution(tmp_path):
    # file lacks column "extra"; read schema requests it -> nulls
    pq.write_table(pa.Table.from_pandas(
        pd.DataFrame({"x": np.arange(4, dtype=np.int64)})),
        tmp_path / "f.parquet")
    want = T.Schema.of(("x", T.INT64), ("extra", T.FLOAT64))
    scan = tio.read_parquet(str(tmp_path / "f.parquet"), want)
    df = collect(accelerate(scan, conf()))
    assert df["extra"].isna().all()
    assert list(df["x"]) == [0, 1, 2, 3]


def test_parquet_fallback_when_disabled(pq_path):
    c = conf().set(C.PARQUET_ENABLED.key, False)
    plan = accelerate(tio.read_parquet(pq_path), c)
    from spark_rapids_tpu.exec.base import TpuExec
    assert not isinstance(plan, TpuExec)  # scan stayed on CPU
    got = collect(plan)
    assert len(got) == 100


# -- orc --------------------------------------------------------------------
def test_orc_scan_parity(tmp_path):
    from pyarrow import orc
    df = _sample_df(60)
    p = tmp_path / "data.orc"
    orc.write_table(pa.Table.from_pandas(df), str(p))
    compare(tio.read_orc(str(p)))


# -- csv --------------------------------------------------------------------
def test_csv_scan_parity(tmp_path):
    p = tmp_path / "data.csv"
    with open(p, "w") as f:
        f.write("i,f,s\n")
        for i in range(50):
            s = "" if i % 7 == 0 else f"v{i}"
            f.write(f"{i},{i * 0.5},{s}\n")
    schema = T.Schema.of(("i", T.INT64), ("f", T.FLOAT64), ("s", T.STRING))
    scan = tio.read_csv(str(p), schema, CsvOptions(header=True))
    plan = compare(scan)
    assert isinstance(plan, TpuFileSourceScanExec)


def test_csv_unsupported_options_fall_back(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("a;b\n1;2\n")
    schema = T.Schema.of(("a", T.INT64), ("b", T.INT64))
    scan = tio.read_csv(str(p), schema, CsvOptions(sep=";;"))
    plan = accelerate(scan, conf())
    from spark_rapids_tpu.exec.base import TpuExec
    assert not isinstance(plan, TpuExec)


def test_csv_split_line_boundaries(tmp_path):
    # force multiple splits over one file; rows must not be lost/duplicated
    p = tmp_path / "big.csv"
    with open(p, "w") as f:
        for i in range(2000):
            f.write(f"{i},{'x' * (i % 37)}\n")
    schema = T.Schema.of(("i", T.INT64), ("s", T.STRING))
    c = conf().set(C.MAX_PARTITION_BYTES.key, 4096).set(
        C.FILE_OPEN_COST.key, 0)
    C.set_active_conf(c)
    try:
        scan = ScanDescription(str(p), "csv", schema, CsvOptions(), conf=c)
        assert len(scan.partitions) > 1
        exec_ = TpuFileSourceScanExec(scan, conf=c)
        got = sorted(
            v for b in exec_.execute_columnar()
            for v in b.column("i").to_pylist(b.num_rows))
        assert got == list(range(2000))
    finally:
        C.set_active_conf(C.RapidsConf())


# -- write path -------------------------------------------------------------
def test_parquet_write_roundtrip(tmp_path):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    df = _sample_df(40)
    out = str(tmp_path / "out")
    batch = ColumnarBatch.from_pandas(df)
    stats = write_batches(iter([batch]), out, "parquet", batch.schema)
    assert stats.num_files == 1 and stats.num_rows == 40
    assert os.path.exists(os.path.join(out, "_SUCCESS"))
    back = collect(accelerate(tio.read_parquet(out), conf()))
    assert len(back) == 40
    assert list(back["i"]) == list(range(40))


def test_write_exec_plan_parity(tmp_path):
    df = _sample_df(30)
    from spark_rapids_tpu.plan import CpuSource
    out = str(tmp_path / "o1")
    node = tio.write(CpuSource.from_pandas(df, num_partitions=2), out,
                     "parquet")
    plan = accelerate(node, conf())
    from spark_rapids_tpu.io.exec import TpuWriteFilesExec
    assert isinstance(plan, TpuWriteFilesExec)
    res = collect(plan)
    assert int(res["num_rows"][0]) == 30
    back = collect(accelerate(tio.read_parquet(out), conf()))
    assert sorted(back["i"]) == list(range(30))


def test_dynamic_partition_write(tmp_path):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    df = pd.DataFrame({
        "k": ["a", "b", "a", None, "b", "a"],
        "v": np.arange(6, dtype=np.int64)})
    out = str(tmp_path / "parted")
    batch = ColumnarBatch.from_pandas(df)
    stats = write_batches(iter([batch]), out, "parquet", batch.schema,
                          partition_by=["k"])
    assert os.path.isdir(os.path.join(out, "k=a"))
    assert os.path.isdir(os.path.join(out, "k=b"))
    assert os.path.isdir(os.path.join(out, "k=__HIVE_DEFAULT_PARTITION__"))
    assert stats.num_rows == 6
    back = collect(accelerate(tio.read_parquet(out), conf()))
    assert sorted(back["v"]) == list(range(6))


def test_orc_write_roundtrip(tmp_path):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    df = _sample_df(25)
    out = str(tmp_path / "orcout")
    batch = ColumnarBatch.from_pandas(df)
    stats = write_batches(iter([batch]), out, "orc", batch.schema)
    assert stats.num_rows == 25
    back = collect(accelerate(tio.read_orc(out), conf()))
    assert len(back) == 25


def test_write_mode_error_and_overwrite(tmp_path):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    df = pd.DataFrame({"x": np.arange(3, dtype=np.int64)})
    out = str(tmp_path / "m")
    b = ColumnarBatch.from_pandas(df)
    write_batches(iter([b]), out, "parquet", b.schema)
    with pytest.raises(FileExistsError):
        write_batches(iter([b]), out, "parquet", b.schema)
    write_batches(iter([b]), out, "parquet", b.schema, mode="overwrite")
    back = collect(accelerate(tio.read_parquet(out), conf()))
    assert len(back) == 3


def test_csv_partitioned_dataset(tmp_path):
    # partition column in the user schema but not in the files
    for year in (2020, 2021):
        d = tmp_path / f"year={year}"
        d.mkdir()
        with open(d / "p.csv", "w") as f:
            for i in range(4):
                f.write(f"{i},{year}-v{i}\n")
    schema = T.Schema.of(("i", T.INT64), ("s", T.STRING),
                         ("year", T.INT64))
    scan = tio.read_csv(str(tmp_path), schema, CsvOptions())
    assert scan.output_schema().names == ("i", "s", "year")
    df = collect(accelerate(scan, conf()))
    assert len(df) == 8
    assert sorted(df["year"].unique()) == [2020, 2021]


def test_write_unsupported_format_does_not_destroy_output(tmp_path):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    out = str(tmp_path / "keep")
    df = pd.DataFrame({"x": np.arange(3, dtype=np.int64)})
    b = ColumnarBatch.from_pandas(df)
    write_batches(iter([b]), out, "parquet", b.schema)
    with pytest.raises(ValueError, match="unsupported write format"):
        write_batches(iter([b]), out, "csv", b.schema, mode="overwrite")
    # the existing parquet output survived the failed overwrite
    back = collect(accelerate(tio.read_parquet(out), conf()))
    assert len(back) == 3


# --- hybrid-calendar rebase (reference RebaseHelper.scala,
# GpuParquetScan.scala:194-249, GpuParquetFileFormat.scala:216-228) -------
def _legacy_day(y, m, d):
    """Day number a Spark 2.x (hybrid-calendar) writer stores for a
    pre-cutover date label."""
    from spark_rapids_tpu.io import rebase as RB
    return int(RB._jdn_from_ymd(np.int64(y), np.int64(m), np.int64(d),
                                julian=True) - RB._EPOCH_JDN)


def _write_legacy_file(path):
    stored = _legacy_day(1200, 1, 1)
    tbl = pa.table({
        "d": pa.array([stored, -100, None], pa.int32()).cast(pa.date32()),
        "x": pa.array([1, 2, 3], pa.int64())})
    pq.write_table(tbl, str(path))
    return stored


def test_parquet_rebase_exception_read_raises(tmp_path):
    """EXCEPTION read mode raises the Spark-3.0 upgrade error on legacy
    files holding pre-1582 dates (RebaseHelper.newRebaseExceptionInRead)."""
    from spark_rapids_tpu.io import rebase as RB
    _write_legacy_file(tmp_path / "t.parquet")
    scan = tio.read_parquet(str(tmp_path))
    plan = accelerate(scan, conf())
    with pytest.raises(RB.SparkUpgradeError, match="1582-10-15"):
        collect(plan)


def test_parquet_rebase_corrected_reads_verbatim(tmp_path):
    stored = _write_legacy_file(tmp_path / "t.parquet")
    key = "spark.sql.legacy.parquet.datetimeRebaseModeInRead"
    c = conf(**{key: "CORRECTED"})
    df = collect(accelerate(tio.read_parquet(str(tmp_path)), c))
    assert int(df["d"].iloc[0]) == stored


def test_parquet_rebase_legacy_cpu_engine_rebases(tmp_path):
    """LEGACY read falls back to the CPU engine (existing test), and that
    engine performs the actual Julian->Gregorian rebase like CPU Spark's
    RebaseDateTime: the pre-cutover *label* is preserved."""
    from spark_rapids_tpu.plan.nodes import CpuNode
    _write_legacy_file(tmp_path / "t.parquet")
    key = "spark.sql.legacy.parquet.datetimeRebaseModeInRead"
    c = conf(**{key: "LEGACY"})
    plan = accelerate(tio.read_parquet(str(tmp_path)), c)
    assert isinstance(plan, CpuNode)
    df = collect(plan)
    want = (datetime.date(1200, 1, 1) - datetime.date(1970, 1, 1)).days
    assert int(df["d"].iloc[0]) == want
    assert int(df["d"].iloc[1]) == -100  # post-cutover rows untouched


def test_parquet_rebase_unknown_mode_falls_back(tmp_path):
    from spark_rapids_tpu.plan.nodes import CpuNode
    _write_legacy_file(tmp_path / "t.parquet")
    key = "spark.sql.legacy.parquet.datetimeRebaseModeInRead"
    plan = accelerate(tio.read_parquet(str(tmp_path)),
                      conf(**{key: "BOGUS"}))
    assert isinstance(plan, CpuNode)


def test_parquet_rebase_write_exception_and_legacy(tmp_path):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.io import rebase as RB
    from spark_rapids_tpu.io.parquet import (
        ParquetColumnarWriter, ParquetWriterOptions)
    schema = T.Schema.of(("d", T.DATE32), ("x", T.INT64))
    gre_day = (datetime.date(1200, 1, 1) - datetime.date(1970, 1, 1)).days
    batch = ColumnarBatch.from_numpy(
        {"d": np.array([gre_day, 0], np.int32),
         "x": np.array([7, 8], np.int64)}, schema)
    # EXCEPTION (the Spark default) raises on pre-cutover values
    w = ParquetColumnarWriter(str(tmp_path / "e.parquet"), schema,
                              ParquetWriterOptions(rebase_mode="EXCEPTION"))
    with pytest.raises(RB.SparkUpgradeError, match="1582-10-15"):
        w.write_batch(batch)
    # LEGACY writes the Julian encoding + the legacyDateTime marker, and
    # a LEGACY read round-trips to the original labels
    p = str(tmp_path / "l.parquet")
    w2 = ParquetColumnarWriter(p, schema,
                               ParquetWriterOptions(rebase_mode="LEGACY"))
    w2.write_batch(batch)
    w2.close()
    md = pq.ParquetFile(p).metadata.metadata
    assert RB.SPARK_LEGACY_DATETIME_KEY in md
    assert pq.read_table(p).column("d").cast(pa.int32()).to_pylist()[0] == \
        _legacy_day(1200, 1, 1)
    from spark_rapids_tpu.io.parquet import ParquetFormat
    t = ParquetFormat("LEGACY").read_split(
        FileSplit(p, 0, os.path.getsize(p), ()), schema, None)
    assert t.column("d").cast(pa.int32()).to_pylist() == [gre_day, 0]


def test_parquet_rebase_corrected_files_skip_checks(tmp_path):
    """Files stamped with a Spark >= 3.0.0 version key and no legacy
    marker are proleptic already — EXCEPTION mode reads them fine
    (GpuParquetScan.scala:199-210)."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.io.parquet import (
        ParquetColumnarWriter, ParquetFormat, ParquetWriterOptions)
    schema = T.Schema.of(("d", T.DATE32), ("x", T.INT64))
    gre_day = (datetime.date(1200, 1, 1) - datetime.date(1970, 1, 1)).days
    batch = ColumnarBatch.from_numpy(
        {"d": np.array([gre_day, 0], np.int32),
         "x": np.array([7, 8], np.int64)}, schema)
    p = str(tmp_path / "c.parquet")
    w = ParquetColumnarWriter(p, schema,
                              ParquetWriterOptions(rebase_mode="CORRECTED"))
    w.write_batch(batch)
    w.close()
    t = ParquetFormat("EXCEPTION").read_split(
        FileSplit(p, 0, os.path.getsize(p), ()), schema, None)
    assert t.column("d").cast(pa.int32()).to_pylist() == [gre_day, 0]


def test_rebase_timestamp_micros_roundtrip():
    from spark_rapids_tpu.io import rebase as RB
    rng = np.random.default_rng(3)
    micros = rng.integers(-130_000_000_000, -119_000_000_000,
                          200).astype(np.int64) * 1_000_000
    leg = RB.rebase_gregorian_to_julian_micros(micros)
    back = RB.rebase_julian_to_gregorian_micros(leg)
    np.testing.assert_array_equal(back, micros)
    # intra-day component survives the rebase
    assert ((leg % 86400000000) == (micros % 86400000000)).all()


def test_parquet_rebase_default_is_shim_versioned(tmp_path):
    """Spark 3.0.0's boolean-era rebase keys default to false (read
    verbatim = CORRECTED); 3.0.1+ mode keys default to EXCEPTION — the
    shim layer owns the default (reference shims encode per-version
    behavior drift)."""
    stored = _write_legacy_file(tmp_path / "t.parquet")
    c300 = conf(**{"spark.rapids.tpu.sparkVersion": "3.0.0"})
    df = collect(accelerate(tio.read_parquet(str(tmp_path)), c300))
    assert int(df["d"].iloc[0]) == stored  # verbatim, no raise


def test_exception_mode_accepts_1582_to_1900_timestamps():
    """ADVICE r1 (medium): UTC sessions have no Julian drift after
    1582-10-15, so an 1850 timestamp must read/write cleanly under the
    default EXCEPTION mode — only pre-1582-10-15 values are ambiguous."""
    import pyarrow as pa
    from spark_rapids_tpu.io import rebase as RB
    micros_1850 = -3786825600000000  # 1850-01-01T00:00:00Z
    tbl = pa.table({"t": pa.array([micros_1850], pa.timestamp("us"))})
    assert not RB.arrow_table_needs_rebase(tbl)
    micros_1500 = -14830986000000000  # ~1500 CE, pre-cutover
    tbl2 = pa.table({"t": pa.array([micros_1500], pa.timestamp("us"))})
    assert RB.arrow_table_needs_rebase(tbl2)


# -- task-commit protocol (GpuFileFormatWriter.scala:338 /
# -- GpuInsertIntoHadoopFsRelationCommand semantics) -------------------------
def _wb(df):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    return ColumnarBatch.from_pandas(df)


def test_write_abort_mid_task_leaves_no_partial_files(tmp_path):
    """A task that dies mid-write must leave NO files in the output:
    its attempt dir is private and abort removes it."""
    from spark_rapids_tpu.io.writer import WriteJob
    df = _sample_df(20)
    out = str(tmp_path / "o")
    b = _wb(df)
    job = WriteJob(out, "parquet", b.schema)
    job.setup()
    w0 = job.task_writer(0)
    w0.write(b)
    stats0 = w0.commit()          # task 0 commits fine
    w1 = job.task_writer(1)
    w1.write(b)                   # task 1 dies before commit
    w1.abort()
    total = job.commit([stats0])
    assert total.num_rows == 20   # only task 0's rows
    files = [n for n in os.listdir(out) if n.endswith(".parquet")]
    assert len(files) == 1 and files[0].startswith("part-00000-")
    assert not os.path.exists(os.path.join(out, "_temporary"))


def test_write_speculative_duplicate_task_commits_once(tmp_path):
    """Two attempts of the SAME task id (speculation): exactly one
    commit wins; the loser's files and stats are discarded."""
    from spark_rapids_tpu.io.writer import WriteJob
    df = _sample_df(10)
    out = str(tmp_path / "o")
    b = _wb(df)
    job = WriteJob(out, "parquet", b.schema)
    job.setup()
    a1 = job.task_writer(0)
    a2 = job.task_writer(0)       # speculative duplicate
    a1.write(b)
    a2.write(b)
    s1 = a1.commit()
    s2 = a2.commit()              # loses the rename race
    assert s1.num_rows == 10 and s2.num_rows == 0
    total = job.commit([s1, s2])
    assert total.num_rows == 10
    files = [n for n in os.listdir(out) if n.endswith(".parquet")]
    assert len(files) == 1


def test_dynamic_partition_overwrite(tmp_path):
    """mode=dynamic_overwrite replaces ONLY the partitions present in
    the new data (Spark partitionOverwriteMode=dynamic; reference
    GpuInsertIntoHadoopFsRelationCommand dynamicPartitionOverwrite)."""
    out = str(tmp_path / "parted")
    df1 = pd.DataFrame({"k": ["a", "b"], "v": np.array([1, 2], np.int64)})
    write_batches(iter([_wb(df1)]), out, "parquet", _wb(df1).schema,
                  partition_by=["k"])
    # overwrite only partition a with new data; b must survive
    df2 = pd.DataFrame({"k": ["a", "a"], "v": np.array([7, 8], np.int64)})
    write_batches(iter([_wb(df2)]), out, "parquet", _wb(df2).schema,
                  partition_by=["k"], mode="dynamic_overwrite")
    back = collect(accelerate(tio.read_parquet(out), conf()))
    got = {(r["k"], int(r["v"])) for _, r in back.iterrows()}
    assert got == {("a", 7), ("a", 8), ("b", 2)}


def test_dynamic_overwrite_requires_partitioning(tmp_path):
    from spark_rapids_tpu.io.writer import WriteJob
    df = _sample_df(5)
    b = _wb(df)
    import pytest
    with pytest.raises(ValueError):
        WriteJob(str(tmp_path / "x"), "parquet", b.schema,
                 mode="dynamic_overwrite")
