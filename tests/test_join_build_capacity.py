"""The join's build side at the capacity of its ROWS.

An exchange's hash split hands the join full-capacity slices whose row
counts are still device scalars; concatenated as they come, the build
side gets the bucketed sum of their capacities (at TPC-H SF0.25:
4,194,304 slots for 807,274 rows) and every kernel after it runs at
that.  `HashJoinExec._concat_build` therefore reads the counts in ONE
stacked device-to-host read (`join.build`) when the padding would pass
one batch, and concatenates tight; known counts and a lazy build within
one batch ask nothing.  Slices are 256 slots wide here and
`batchMaxRows` is 256, so the rule engages at a size that compiles in a
second.
"""
import numpy as np
import pandas as pd
import pytest

jnp = pytest.importorskip("jax.numpy")

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.vector import bucket_capacity
from spark_rapids_tpu.exec.basic import LocalBatchSource
from spark_rapids_tpu.exec.joins import HashJoinExec, JoinType
from spark_rapids_tpu.exprs.base import col
from spark_rapids_tpu.utils import checks as CK

SLICE_CAP = 256
CONF = {"spark.rapids.tpu.batchMaxRows": SLICE_CAP}

#: build shape -> rows of each slice, whether the counts stay on the
#: device, and the count reads the join is to make
SHAPES = {
    # 8 x 256 slots for 320 rows: lazy concat 2,048, tight 512
    "lazy-slices": ([40] * 8, True, 1),
    "lazy-slices-some-empty": ([0, 70, 0, 90, 60, 0, 100, 0], True, 1),
    "known-counts": ([40] * 8, False, 0),
    # 2 x 128 slots: the lazy concat's 256 is within one batch
    "lazy-within-one-batch": ([50, 60], True, 0),
}

JOINS = {
    JoinType.INNER: "inner", JoinType.LEFT_OUTER: "left",
    JoinType.RIGHT_OUTER: "right", JoinType.FULL_OUTER: "outer",
    JoinType.LEFT_SEMI: "semi", JoinType.LEFT_ANTI: "anti"}

CASES = [(jt, shape) for jt in JOINS
         for shape in ("lazy-slices", "lazy-slices-some-empty")] + \
    [(JoinType.INNER, "known-counts"),
     (JoinType.INNER, "lazy-within-one-batch")]


def _build_frame(rows: list[int]) -> pd.DataFrame:
    n = sum(rows)
    rng = np.random.default_rng(n)
    # keys repeat (the sort path, as q3's l_orderkey and o_custkey) and
    # half of their range never meets the probe side's
    return pd.DataFrame({"bk": rng.integers(10, 50, n).astype(np.int64),
                         "bv": np.arange(n, dtype=np.int64) + 1000})


def _probe_frame() -> pd.DataFrame:
    rng = np.random.default_rng(7)
    return pd.DataFrame({"pk": rng.integers(0, 30, 90).astype(np.int64),
                         "pv": np.arange(90, dtype=np.int64)})


def _slices(frame: pd.DataFrame, rows: list[int], lazy: bool):
    """The frame cut into full-capacity batches of `rows` rows each, as
    an exchange's split cuts them: `lazy` keeps each count a device
    scalar."""
    cap = SLICE_CAP if len(rows) > 2 else SLICE_CAP // 2
    out, lo = [], 0
    for n in rows:
        part = frame.iloc[lo:lo + n]
        lo += n
        b = ColumnarBatch.from_numpy(
            {c: part[c].to_numpy() for c in frame.columns}, capacity=cap)
        out.append(ColumnarBatch(b.schema, b.columns, jnp.int32(n))
                   if lazy else b)
    return out


def _plan(jt: JoinType, build_batches) -> HashJoinExec:
    probe = LocalBatchSource.from_pandas(_probe_frame(), num_partitions=2)
    # two partitions a side, as the join takes every partition's slices
    half = len(build_batches) // 2
    build = LocalBatchSource([build_batches[:half], build_batches[half:]])
    if jt == JoinType.RIGHT_OUTER:      # the LEFT side is the build side
        return HashJoinExec(jt, [col("bk")], [col("pk")], build, probe)
    return HashJoinExec(jt, [col("pk")], [col("bk")], probe, build)


def _expected(jt: JoinType, build: pd.DataFrame) -> pd.DataFrame:
    probe = _probe_frame()
    if jt == JoinType.LEFT_SEMI:
        return probe[probe["pk"].isin(build["bk"])]
    if jt == JoinType.LEFT_ANTI:
        return probe[~probe["pk"].isin(build["bk"])]
    if jt == JoinType.RIGHT_OUTER:
        return build.merge(probe, left_on="bk", right_on="pk", how="right")
    return probe.merge(build, left_on="pk", right_on="bk", how=JOINS[jt])


def _rows(frame: pd.DataFrame) -> list[tuple]:
    cols = sorted(frame.columns)
    return sorted(map(tuple, frame[cols].astype("float64").fillna(-1)
                      .to_numpy().tolist()))


@pytest.mark.parametrize(
    "jt,shape", CASES, ids=[f"{JOINS[jt]}-{shape}" for jt, shape in CASES])
def test_build_side_gets_the_capacity_of_its_rows(jt, shape):
    rows, lazy, want_reads = SHAPES[shape]
    frame = _build_frame(rows)
    with C.session(C.RapidsConf(CONF)):
        # the build side alone: its capacity and what it asked the device
        plan = _plan(jt, _slices(frame, rows, lazy))
        before = CK.host_sync_sites().get("join.build", 0)
        batches = plan._collect_build_batches()
        assert len(batches) == len(rows)
        build, reads = plan._concat_build(batches)
        assert reads == want_reads
        assert CK.host_sync_sites().get("join.build", 0) - before == reads
        lazy_cap = bucket_capacity(sum(b.capacity for b in batches))
        if reads:
            assert build.num_rows_known and build.num_rows == sum(rows)
            assert build.capacity == bucket_capacity(sum(rows)) < lazy_cap
        elif lazy:      # within one batch: as before, padded and unasked
            assert not build.num_rows_known
            assert build.capacity == lazy_cap == SLICE_CAP
        else:           # counts known: tight as before
            assert build.capacity == bucket_capacity(sum(rows))
        # the whole join, from fresh slices: pandas' answer
        before = CK.host_sync_sites().get("join.build", 0)
        got = _plan(jt, _slices(frame, rows, lazy)).to_pandas()
        assert CK.host_sync_sites().get("join.build", 0) - before == reads
    assert _rows(got) == _rows(_expected(jt, frame))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_single_batch_coalesce_follows_the_same_rule(shape):
    """The route a build side takes under AQE, and a global sort's or a
    window's in any plan: `coalesce_iterator`'s `RequireSingleBatch`
    branch gives the batch `_concat_build` gives."""
    from spark_rapids_tpu.exec.base import RequireSingleBatch
    from spark_rapids_tpu.exec.coalesce import coalesce_iterator
    from spark_rapids_tpu.utils.metrics import MetricSet
    rows, lazy, want_reads = SHAPES[shape]
    frame = _build_frame(rows)
    batches = _slices(frame, rows, lazy)
    before = CK.host_sync_sites().get("coalesce.single", 0)
    (out,) = coalesce_iterator(iter(batches), RequireSingleBatch(),
                               batches[0].schema, MetricSet(),
                               max_rows=SLICE_CAP)
    reads = CK.host_sync_sites().get("coalesce.single", 0) - before
    assert reads == want_reads
    with C.session(C.RapidsConf(CONF)):
        fresh = _slices(frame, rows, lazy)
        build, _ = _plan(JoinType.INNER, fresh)._concat_build(fresh)
    assert out.capacity == build.capacity
    assert out.num_rows_known == build.num_rows_known
    pd.testing.assert_frame_equal(out.to_pandas(), frame)
