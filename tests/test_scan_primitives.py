"""Regression coverage for the round-5 kernel primitives: the
hand-rolled segmented scan, top_k-based masked positions, and the
pid-sort partition reorder (the first two replaced
lax.associative_scan and jnp.nonzero)."""
import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.vector import ColumnVector
from spark_rapids_tpu.exprs.aggregates import _segscan
from spark_rapids_tpu.ops.sort_encode import masked_positions


def _np_segscan_sum(flags, vals):
    out = np.zeros_like(vals)
    acc = 0
    for i in range(len(vals)):
        acc = vals[i] if flags[i] else acc + vals[i]
        out[i] = acc
    return out


@pytest.mark.parametrize("n", [1, 2, 3, 7, 64, 100, 1023])
def test_segscan_sum_matches_numpy(n):
    rng = np.random.default_rng(n)
    flags = rng.random(n) < 0.2
    flags[0] = True
    vals = rng.integers(-50, 50, n).astype(np.int64)
    (got,) = _segscan(lambda a, b: (a[0] + b[0],),
                      jnp.asarray(flags), jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(got),
                                  _np_segscan_sum(flags, vals))


def test_segscan_multi_operand_mixed_dtypes():
    """Several value operands ride ONE scan — the capability the
    tuple-carry associative_scan could not compile at scale."""
    n = 257  # odd, exercises the per-level padding
    rng = np.random.default_rng(9)
    flags = rng.random(n) < 0.3
    flags[0] = True
    a = rng.uniform(-1, 1, n)
    b = rng.integers(0, 100, n).astype(np.int32)
    ga, gb = _segscan(lambda x, y: (x[0] + y[0], x[1] + y[1]),
                      jnp.asarray(flags), jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(ga), _np_segscan_sum(flags, a),
                               rtol=1e-12)
    np.testing.assert_array_equal(np.asarray(gb),
                                  _np_segscan_sum(flags, b))


def test_segscan_minmax_combine():
    n = 100
    rng = np.random.default_rng(3)
    flags = rng.random(n) < 0.25
    flags[0] = True
    vals = rng.integers(-1000, 1000, n).astype(np.int64)
    (got,) = _segscan(lambda x, y: (jnp.minimum(x[0], y[0]),),
                      jnp.asarray(flags), jnp.asarray(vals))
    exp = np.zeros_like(vals)
    acc = 0
    for i in range(n):
        acc = vals[i] if flags[i] else min(acc, vals[i])
        exp[i] = acc
    np.testing.assert_array_equal(np.asarray(got), exp)


@pytest.mark.parametrize("n_set", [0, 1, 5, 100])
def test_masked_positions(n_set):
    cap, size = 1024, 64
    rng = np.random.default_rng(n_set)
    mask = np.zeros(cap, bool)
    idx = np.sort(rng.choice(cap, n_set, replace=False))
    mask[idx] = True
    got = np.asarray(masked_positions(jnp.asarray(mask), size,
                                      fill_value=cap - 1))
    exp = np.full(size, cap - 1)
    exp[: min(n_set, size)] = idx[:size]
    np.testing.assert_array_equal(got, exp)


def test_masked_positions_payload_sort_lane():
    """size past MASKED_POSITIONS_TOPK_MAX takes the 1-bit payload
    sort; identical contract."""
    from spark_rapids_tpu.ops.sort_encode import \
        MASKED_POSITIONS_TOPK_MAX
    cap = MASKED_POSITIONS_TOPK_MAX * 8
    size = MASKED_POSITIONS_TOPK_MAX * 2
    rng = np.random.default_rng(11)
    idx = np.sort(rng.choice(cap, size + 100, replace=False))
    mask = np.zeros(cap, bool)
    mask[idx] = True
    got = np.asarray(masked_positions(jnp.asarray(mask), size,
                                      fill_value=cap - 1))
    np.testing.assert_array_equal(got, idx[:size])
    # and with fewer set bits than size: fill past the count
    mask2 = np.zeros(cap, bool)
    mask2[idx[:50]] = True
    got2 = np.asarray(masked_positions(jnp.asarray(mask2), size,
                                       fill_value=cap - 1))
    np.testing.assert_array_equal(got2[:50], idx[:50])
    assert (got2[50:] == cap - 1).all()


def test_masked_positions_full_width_path():
    """size*2 > cap takes the nonzero fallback; same contract."""
    cap = 64
    mask = np.zeros(cap, bool)
    mask[[3, 10, 63]] = True
    got = np.asarray(masked_positions(jnp.asarray(mask), cap,
                                      fill_value=cap - 1))
    assert got[:3].tolist() == [3, 10, 63]
    assert (got[3:] == cap - 1).all()


def test_payload_sort_reorder_with_strings_and_nulls():
    """The pid-sort reorder moves every column kind (i64+narrow,
    f64, bool validity, string char matrices via the sorted order)
    and is STABLE within a partition."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.shuffle.partitioning import \
        _pid_sort_reorder
    n = 40
    rng = np.random.default_rng(5)
    pids_np = rng.integers(0, 4, n).astype(np.int32)
    df_k = rng.integers(-5, 5, n).astype(np.int64)
    df_v = rng.uniform(-1, 1, n)
    strs = np.array([None if i % 7 == 0 else f"s{i:02d}" for i in
                     range(n)], dtype=object)
    b = ColumnarBatch.from_numpy(
        {"k": df_k, "v": df_v, "s": strs})
    cap = b.capacity
    pids = jnp.asarray(np.pad(pids_np, (0, cap - n),
                              constant_values=4)).astype(jnp.uint32)
    row_mask = jnp.arange(cap) < n
    cols, counts = _pid_sort_reorder(pids, b.columns, row_mask, 4)
    counts = np.asarray(counts)
    np.testing.assert_array_equal(counts,
                                  np.bincount(pids_np, minlength=4))
    # reassemble and compare against the numpy stable sort
    order = np.argsort(pids_np, kind="stable")
    out_k, vk = ColumnVector.to_numpy(cols[0], n)
    out_v, _ = ColumnVector.to_numpy(cols[1], n)
    out_s, vs = ColumnVector.to_numpy(cols[2], n)
    np.testing.assert_array_equal(out_k, df_k[order])
    np.testing.assert_allclose(out_v, df_v[order], rtol=1e-12)
    assert [out_s[i] if vs[i] else None for i in range(n)] == \
        [strs[order[i]] for i in range(n)]
    # narrow shadow survived the reorder consistently
    if cols[0].narrow is not None:
        np.testing.assert_array_equal(
            np.asarray(cols[0].narrow)[:n], df_k[order].astype(np.int32))
