"""Columnar substrate tests (reference analogs: GpuColumnVector round-trip,
GpuCoalesceBatchesSuite concat)."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, concat_batches
from spark_rapids_tpu.columnar.vector import (
    ColumnVector, _strings_from_host, bucket_capacity)


def test_bucket_capacity():
    assert bucket_capacity(0) == 32
    assert bucket_capacity(32) == 32
    assert bucket_capacity(33) == 64
    assert bucket_capacity(1000) == 1024


def test_int_roundtrip():
    v = ColumnVector.from_numpy(np.array([1, 2, 3], np.int64))
    assert v.capacity == 32
    vals, validity = v.to_numpy(3)
    np.testing.assert_array_equal(vals, [1, 2, 3])
    assert validity.all()


def test_null_roundtrip():
    v = ColumnVector.from_numpy(
        np.array([1, 2, 3], np.int64),
        validity=np.array([True, False, True]))
    assert v.to_pylist(3) == [1, None, 3]


def test_string_roundtrip():
    vals = np.array(["hello", "", None, "world…"], dtype=object)
    v = ColumnVector.from_numpy(vals)
    assert v.dtype == T.STRING
    assert v.to_pylist(4) == ["hello", "", None, "world…"]


def test_batch_from_pandas_roundtrip():
    df = pd.DataFrame({
        "a": [1, 2, 3],
        "b": [1.5, np.nan, 3.0],
        "s": ["x", None, "zzz"],
    })
    batch = ColumnarBatch.from_pandas(df)
    out = batch.to_pandas()
    np.testing.assert_array_equal(out["a"], [1, 2, 3])
    assert out["s"].tolist() == ["x", None, "zzz"]
    # pandas NaN maps to null through from_pandas (pandas conflates them)
    assert out["b"][1] is None


def test_batch_from_arrow_roundtrip():
    import pyarrow as pa
    t = pa.table({
        "i": pa.array([1, None, 3], pa.int32()),
        "f": pa.array([1.0, 2.0, None], pa.float64()),
        "s": pa.array(["a", None, "c"]),
    })
    batch = ColumnarBatch.from_arrow(t)
    assert batch.num_rows == 3
    assert batch.column("i").to_pylist(3) == [1, None, 3]
    assert batch.column("f").to_pylist(3) == [1.0, 2.0, None]
    assert batch.column("s").to_pylist(3) == ["a", None, "c"]
    t2 = batch.to_arrow()
    assert t2.column("i").to_pylist() == [1, None, 3]


def test_concat_batches():
    b1 = ColumnarBatch.from_numpy({"x": np.arange(5, dtype=np.int64)})
    b2 = ColumnarBatch.from_numpy({"x": np.arange(5, 8, dtype=np.int64)})
    out = concat_batches([b1, b2])
    assert out.num_rows == 8
    assert out.column("x").to_pylist(8) == list(range(8))


def test_concat_strings_different_widths():
    b1 = ColumnarBatch.from_numpy(
        {"s": np.array(["a", "bb"], dtype=object)})
    b2 = ColumnarBatch.from_numpy(
        {"s": np.array(["a-very-long-string-here", None], dtype=object)})
    out = concat_batches([b1, b2])
    assert out.column("s").to_pylist(4) == [
        "a", "bb", "a-very-long-string-here", None]


def test_slice():
    b = ColumnarBatch.from_numpy({"x": np.arange(10, dtype=np.int64)})
    s = b.slice(3, 4)
    assert s.num_rows == 4
    assert s.column("x").to_pylist(4) == [3, 4, 5, 6]


def test_f32_shadow_overflow_boundaries():
    """The FLOAT64 narrow shadow's overflow semantics are explicit:
    finite f64 past the f32 range clamps to +-f32max
    (monotone, finiteness-preserving), infinities and NaN pass
    through, signs (incl. -0.0) are kept — and no RuntimeWarning."""
    import warnings
    fmax64 = float(np.finfo(np.float32).max)
    vals = np.array([1e308, -1e308, fmax64, -fmax64, fmax64 * 2,
                     np.inf, -np.inf, np.nan, 0.0, -0.0, 1.5])
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        cv = ColumnVector.from_numpy(vals, T.FLOAT64)
    n = np.asarray(cv.narrow)[: len(vals)]
    fmax = np.float32(np.finfo(np.float32).max)
    assert n[0] == fmax and n[1] == -fmax          # clamped, finite
    assert n[2] == fmax and n[3] == -fmax          # exact boundary
    assert n[4] == fmax                            # just past boundary
    assert np.isposinf(n[5]) and np.isneginf(n[6])  # inf passes through
    assert np.isnan(n[7])
    assert n[8] == 0.0 and np.signbit(n[9])        # -0.0 sign kept
    assert n[10] == np.float32(1.5)
    # monotone: shadow order respects value order on the finite entries
    fin = [0, 1, 2, 3, 4, 8, 9, 10]
    order64 = np.argsort(vals[fin], kind="stable")
    assert (np.diff(n[fin][order64]) >= 0).all()


# ---------------------------------------------------------------------------
# grouped source upload: ColumnarBatch.chunks_from_numpy against the
# chunk-by-chunk from_numpy it stands in for
def _i64(rng, n):
    return rng.integers(-5_000, 5_000, n).astype(np.int64)


def _i64_wide(rng, n):
    v = _i64(rng, n)
    v[n // 2] = np.int64(1) << 40          # one chunk leaves int32
    return v


def _f64_wide(rng, n):
    v = rng.normal(size=n) * 1e3
    v[::97] = 1e300                        # past float32: shadow clamps
    v[1::97] = -np.inf
    v[2::97] = np.nan                      # a value, not a null
    return v


_GROUPED_COLUMNS = {
    "int64": (T.INT64, _i64),
    "int64-past-int32": (T.INT64, _i64_wide),
    "int32": (T.INT32, lambda rng, n: rng.integers(0, 99, n)
              .astype(np.int32)),
    "date32": (T.DATE32, lambda rng, n: rng.integers(8000, 11000, n)
               .astype(np.int32)),
    "bool": (T.BOOL, lambda rng, n: rng.random(n) < 0.5),
    "float64": (T.FLOAT64, lambda rng, n: rng.random(n)),
    "float64-past-float32": (T.FLOAT64, _f64_wide),
    "timestamp": (T.TIMESTAMP_US, lambda rng, n: rng.integers(
        0, 1 << 50, n).astype(np.int64)),
    "string": (T.STRING, lambda rng, n: np.array(
        [None if i % 11 == 0 else "w" * (i % 23) for i in range(n)],
        dtype=object)),
}


def _host_frame(kinds, n, nulls=True):
    rng = np.random.default_rng(n)
    fields, data, validity = [], {}, {}
    for i, kind in enumerate(kinds):
        dtype, make = _GROUPED_COLUMNS[kind]
        name = f"c{i}"
        fields.append(T.Field(name, dtype))
        data[name] = make(rng, n)
        if dtype.is_string:
            validity[name] = np.array([v is not None for v in data[name]])
        else:
            validity[name] = rng.random(n) > 0.1 if nulls \
                else np.ones(n, bool)
    return data, T.Schema(tuple(fields)), validity


def _per_chunk(data, schema, validity, max_rows):
    n = len(next(iter(data.values())))
    return [ColumnarBatch.from_numpy(
        {k: v[lo:lo + max_rows] for k, v in data.items()}, schema,
        validity and {k: v[lo:lo + max_rows] for k, v in validity.items()})
        for lo in range(0, n, max_rows)]


def _assert_same_batches(got, ref, withheld=()):
    """Array-equal batches; a column in `withheld` may lack the INT64
    shadow that only some of its chunks could carry."""
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        assert g.num_rows == r.num_rows and g.capacity == r.capacity
        assert g.schema == r.schema
        for f, cg, cr in zip(g.schema.fields, g.columns, r.columns):
            pairs = [(cg.data, cr.data), (cg.validity, cr.validity),
                     (cg.lengths, cr.lengths)]
            if not (f.name in withheld and cg.narrow is None):
                pairs.append((cg.narrow, cr.narrow))
            for x, y in pairs:
                assert (x is None) == (y is None), f
                if x is not None:
                    assert x.dtype == y.dtype and x.shape == y.shape, f
                    assert np.array_equal(np.asarray(x), np.asarray(y),
                                          equal_nan=x.dtype.kind == "f"), f


@pytest.mark.parametrize("n,max_rows", [(1000, 128), (1024, 128),
                                        (1000, 100)],
                         ids=["ragged-tail", "no-tail", "padded-chunks"])
@pytest.mark.parametrize("kind", list(_GROUPED_COLUMNS))
def test_grouped_upload_equals_per_chunk_from_numpy(kind, n, max_rows):
    kinds = [kind, "int32"] if kind == "string" else [kind]
    data, schema, validity = _host_frame(kinds, n)
    got, sent = ColumnarBatch.chunks_from_numpy(data, schema, validity,
                                                max_rows)
    ref = _per_chunk(data, schema, validity, max_rows)
    wide = kind == "int64-past-int32"
    _assert_same_batches(got, ref, withheld={"c0"} if wide else ())
    if wide:        # decided once a run: withheld everywhere
        assert all(b.columns[0].narrow is None for b in got)
        assert any(b.columns[0].narrow is not None for b in ref)
    per_chunk = sum(c.device_arrays for b in ref for c in b.columns)
    assert 0 < sent < per_chunk
    if kind != "string":
        arrays = got[0].columns[0].device_arrays
        assert sent == arrays * (1 + (n % max_rows > 0))


def _flags(n, long_at):
    """One-character flags with nulls, as q1's two keys are, and one
    value in the chunk of row `long_at` that is past the smallest
    `char_cap` bucket."""
    v = np.array([None if i % 13 == 5 else "ANR"[i % 3] for i in range(n)],
                 dtype=object)
    v[long_at] = "RETURNED-" * 3
    return v


@pytest.mark.parametrize("fixed_kinds,n,max_rows", [
    (["float64", "date32"], 1000, 128),     # grouped: 7 chunks and a tail
    (["float64", "date32"], 1024, 128),     # grouped, no tail
    (["int64"], 200, 128),                  # one full chunk: per chunk
    ([], 1000, 128),                        # string columns alone
], ids=["grouped-ragged-tail", "grouped-no-tail", "under-two-chunks",
        "strings-alone"])
def test_string_columns_of_a_run_equal_per_chunk_from_numpy(
        fixed_kinds, n, max_rows):
    """A run's string columns are encoded once and cut on the device,
    after its fixed-width ones are sent: the batches are still those of
    a chunk-by-chunk `from_numpy` (rows, capacities, `char_cap` per
    chunk, contents), and those of the per-value path; two full chunks
    or more send three arrays a column and run (three more with a
    tail), whatever stands beside them."""
    data, schema, validity = _host_frame(fixed_kinds, n)
    fields = list(schema.fields)
    for name, long_at in (("flag", n // 2), ("status", n - 1)):
        fields.insert(1 if fixed_kinds else 0, T.Field(name, T.STRING))
        data[name] = _flags(n, long_at)
        validity[name] = np.array([v is not None for v in data[name]])
    schema = T.Schema(tuple(fields))
    data = {f.name: data[f.name] for f in fields}
    got, sent = ColumnarBatch.chunks_from_numpy(data, schema, validity,
                                                max_rows)
    ref = _per_chunk(data, schema, validity, max_rows)
    _assert_same_batches(got, ref)
    chunks = -(-n // max_rows)
    assert [b.num_rows for b in got] == [max_rows] * (n // max_rows) + (
        [n % max_rows] if n % max_rows else [])
    for name, long_at in (("flag", n // 2), ("status", n - 1)):
        caps = [b.column(name).char_cap for b in got]
        assert caps == [b.column(name).char_cap for b in ref]
        assert caps[long_at // max_rows] > min(caps) and len(set(caps)) == 2
        for b, lo in zip(got, range(0, n, max_rows)):
            _assert_same_vector(b.column(name), _per_value(
                data[name][lo:lo + max_rows], b.capacity))
    runs = n // max_rows >= 2
    fixed_arrays = sum(c.device_arrays for f, c in
                       zip(schema.fields, got[0].columns)
                       if not f.dtype.is_string)
    tail = n % max_rows > 0
    assert sent == 2 * 3 * (1 + tail if runs else chunks) + fixed_arrays * (
        1 + tail if runs and fixed_kinds else chunks)


def _per_value(values, capacity):
    """The oracle: `_strings_from_host`, a Python call a value."""
    valid = np.array([v is not None for v in values], bool)
    return _strings_from_host(
        np.asarray(values, object), np.pad(valid, (0, capacity - len(valid))),
        capacity)


def _assert_same_vector(got, ref):
    for x, y in ((got.data, ref.data), (got.validity, ref.validity),
                 (got.lengths, ref.lengths)):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(np.asarray(x), np.asarray(y))


_ARROW_STR = pd.StringDtype(na_value=np.nan)


def _nulls_that_span_bytes():
    """An Arrow array whose null slots keep their bytes (a filter or a
    cast may leave such): every third value nulled in the bitmap only."""
    whole = pa.array(["ab", "cde", "f", "", "ghij", "k"] * 50,
                     pa.large_string())
    bitmap = pa.array([i % 3 != 1 for i in range(len(whole))]).buffers()[1]
    return pd.Series(pd.array(pa.Array.from_buffers(
        pa.large_string(), len(whole), [bitmap] + whole.buffers()[1:]),
        dtype=_ARROW_STR))


_WORDS = ["ab", "", "Zoë", None, "日本", "c", np.nan, "defg"]
_STRING_COLUMNS = {
    "arrow-backed": lambda: pd.Series(
        [w for w in _WORDS * 40 if isinstance(w, str)], dtype=_ARROW_STR),
    "object": lambda: pd.Series(
        [w for w in _WORDS * 40 if isinstance(w, str)], dtype=object),
    "arrow-slice-at-an-offset": lambda: pd.Series(
        _WORDS * 60, dtype=_ARROW_STR).iloc[101:401],
    "nulls-none-and-nan-arrow": lambda: pd.Series(_WORDS * 40,
                                                  dtype=_ARROW_STR),
    "nulls-none-and-nan-object": lambda: pd.Series(_WORDS * 40,
                                                   dtype=object),
    "empty-strings": lambda: pd.Series([""] * 300, dtype=_ARROW_STR),
    "all-null-chunk": lambda: pd.Series(
        ["x"] * 128 + [None] * 128 + ["yz"] * 44, dtype=_ARROW_STR),
    "equal-lengths": lambda: pd.Series(list("ANR") * 100,
                                       dtype=_ARROW_STR),
    "multi-byte-utf8": lambda: pd.Series(["Zoë", "日本", "ü", "naïve"] * 75,
                                         dtype=object),
    "one-long-among-short": lambda: pd.Series(
        ["s"] * 200 + ["RETURNED-" * 9] + ["t"] * 99, dtype=_ARROW_STR),
    "zero-rows": lambda: pd.Series([], dtype=_ARROW_STR),
    "nulls-that-span-bytes": _nulls_that_span_bytes,
    "bytes": lambda: pd.Series([b"ab", "c", None, b"Zo\xc3\xab"] * 75,
                               dtype=object),
    "bytes-and-numbers": lambda: pd.Series(
        [b"ab", 12, 3.5, "x", None, b"\xff\xfe"] * 50, dtype=object),
}


@pytest.mark.parametrize("kind", list(_STRING_COLUMNS))
def test_string_encoder_equals_the_per_value_path(kind):
    """A STRING column from pandas to the device through its Arrow
    buffers: one chunk (`from_numpy`) and a run cut on the device
    (`chunks_from_numpy`) are array-equal (bytes, validity, lengths,
    `char_cap`) to `_strings_from_host`, which encodes every value by a
    Python call.  Only what Arrow refuses takes that path: the span's
    `per_value` counts those values."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.plan.transitions import host_columns_from_df
    from spark_rapids_tpu.utils import profile as P
    s = _STRING_COLUMNS[kind]()
    n, max_rows = len(s), 128
    schema = T.Schema((T.Field("s", T.STRING),))
    data, validity = host_columns_from_df(pd.DataFrame({"s": s}), schema)
    refused = kind == "bytes-and-numbers"
    if refused:
        assert data["s"].dtype == object
    else:       # no Python object per value, at a slice's offset too
        assert isinstance(data["s"], pa.LargeStringArray)
        assert (data["s"].offset > 0) == (kind == "arrow-slice-at-an-offset")
    values = [None if null else v for v, null in zip(s.tolist(), s.isna())]
    assert validity["s"].tolist() == [v is not None for v in values]
    one = ColumnVector.from_numpy(data["s"], T.STRING, validity["s"])
    _assert_same_vector(one, _per_value(values, bucket_capacity(n)))
    tr = P.begin_plan(C.RapidsConf({"spark.rapids.sql.profile.enabled": True}))
    try:
        got, sent = ColumnarBatch.chunks_from_numpy(data, schema, validity,
                                                    max_rows)
    finally:
        P.park_plan(tr)
    assert len(got) == -(-n // max_rows)
    for b, lo in zip(got, range(0, n, max_rows)):
        _assert_same_vector(b.columns[0], _per_value(
            values[lo:lo + max_rows], b.capacity))
    spans = [x for x in tr.spans() if x.name == P.SPAN_UPLOAD_STRINGS]
    assert len(spans) == (n > 0)
    if n:
        assert spans[0].args["per_value"] == (n if refused else 0)
        assert spans[0].args["transfers"] == sent == (
            3 * len(got) if refused else 3 * (1 + (n % max_rows > 0)))


def test_grouped_upload_mixed_frame_without_validity():
    """Every kind in one frame, and `validity=None`: all rows valid but
    the None strings, as `from_numpy` reads them."""
    data, schema, _ = _host_frame(list(_GROUPED_COLUMNS), 700)
    got, _ = ColumnarBatch.chunks_from_numpy(data, schema, None, 64)
    _assert_same_batches(got, _per_chunk(data, schema, None, 64),
                         withheld={"c1"})


@pytest.mark.parametrize("n", [0, 1, 100, 128, 200, 255])
def test_fewer_than_two_full_chunks_build_no_split_program(n, monkeypatch):
    from spark_rapids_tpu.columnar import batch as CB

    def no_split(*_a, **_k):
        raise AssertionError("split program built")
    monkeypatch.setattr(CB, "_split_chunks_jit", no_split)
    data, schema, validity = _host_frame(["int64", "float64"], n)
    got, sent = ColumnarBatch.chunks_from_numpy(data, schema, validity, 128)
    _assert_same_batches(got, _per_chunk(data, schema, validity, 128))
    assert len(got) == -(-n // 128)
    assert sent == 6 * len(got)


def test_partition_over_the_byte_budget_goes_in_several_transfers(
        monkeypatch):
    from spark_rapids_tpu.columnar import batch as CB
    data, schema, validity = _host_frame(
        ["int64", "float64", "date32"], 1000, nulls=False)
    one, sent_one = ColumnarBatch.chunks_from_numpy(data, schema, validity,
                                                    64)
    runs = []
    split = CB._split_chunks_jit
    monkeypatch.setattr(
        CB, "_split_chunks_jit",
        lambda arrays, max_rows: runs.append(arrays[0].shape[0])
        or split(arrays, max_rows))
    # 8+5, 8+5, 4+5 bytes a row: five chunks of 64 rows a transfer
    monkeypatch.setattr(CB, "UPLOAD_TRANSFER_BYTES", 5 * 64 * 35)
    got, sent = ColumnarBatch.chunks_from_numpy(data, schema, validity, 64)
    assert runs == [320, 320, 320]          # 1000 = 3 x 320 + 40
    assert sent_one == 2 * 8 and sent == 4 * 8
    _assert_same_batches(got, one)
    _assert_same_batches(got, _per_chunk(data, schema, validity, 64))


def test_grouped_upload_keeps_no_whole_column_on_the_device():
    """Once the batches are built nothing holds the whole columns: what
    stays live on the device is the batches' own arrays."""
    import gc
    import jax
    from spark_rapids_tpu.utils.movement import vector_device_bytes
    data, schema, validity = _host_frame(["int64", "float64"], 5000)

    def live():
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays())
    before = live()
    got, _ = ColumnarBatch.chunks_from_numpy(data, schema, validity, 512)
    assert live() - before == sum(
        vector_device_bytes(c) for b in got for c in b.columns)
