"""A shuffled hash join over co-partitioned exchanges joins partition p
with partition p, where they lie: equal to the whole-build join and to
a plain pandas merge for every join type, with nulls and duplicate
keys, at 1, 2 and 4 partitions; under a four-chip mesh partition p is
uploaded to, exchanged onto and joined on chip p, q3 / q5 answer as
the CPU engine does, and a batch changes chips outside the all-to-all
only through the counted move."""
import jax
import numpy as np
import pandas as pd
import pytest

from parity import compare_frames
from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.basic import LocalBatchSource
from spark_rapids_tpu.exec.joins import HashJoinExec, JoinType
from spark_rapids_tpu.exprs.base import col
from spark_rapids_tpu.parallel import mesh as PM
from spark_rapids_tpu.plan import nodes as N
from spark_rapids_tpu.plan.overrides import accelerate, collect
from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
from spark_rapids_tpu.utils import checks as CK

LEFT = T.Schema.of(("k", T.INT64), ("a", T.INT64))
RIGHT = T.Schema.of(("rk", T.INT64), ("b", T.FLOAT64))
TYPES = [JoinType.INNER, JoinType.LEFT_OUTER, JoinType.RIGHT_OUTER,
         JoinType.FULL_OUTER, JoinType.LEFT_SEMI, JoinType.LEFT_ANTI]


def _tables(seed, rows=96):
    """Seeded sides with duplicate keys on both, keys only one side
    has, and null keys (which match nothing)."""
    rng = np.random.default_rng(seed)
    left = pd.DataFrame({
        "k": rng.integers(0, 24, rows).astype("float"),
        "a": np.arange(rows)})
    right = pd.DataFrame({
        "rk": rng.integers(8, 32, rows).astype("float"),
        "b": rng.normal(size=rows)})
    left.loc[rng.random(rows) < 0.1, "k"] = np.nan
    right.loc[rng.random(rows) < 0.1, "rk"] = np.nan
    return left, right


def _source(df, schema, key, n):
    parts = []
    for rows in np.array_split(np.arange(len(df)), n):
        part = df.iloc[rows]
        data = {f.name: part[f.name].fillna(0).to_numpy(
            f.dtype.storage_dtype) for f in schema.fields}
        valid = {key: part[key].notna().to_numpy()}
        parts.append([ColumnarBatch.from_numpy(data, schema, valid)])
    return LocalBatchSource(parts, schema)


def _join(jt, left, right, n):
    lx = ShuffleExchangeExec(HashPartitioning([col("k")], n),
                             _source(left, LEFT, "k", n))
    rx = ShuffleExchangeExec(HashPartitioning([col("rk")], n),
                             _source(right, RIGHT, "rk", n))
    return HashJoinExec(jt, [col("k")], [col("rk")], lx, rx)


def _pandas_join(jt, left, right):
    """SQL's equi-join in plain pandas: a null key matches nothing."""
    l = left.reset_index(names="li")
    r = right.reset_index(names="ri")
    inner = l[l.k.notna()].merge(r[r.rk.notna()], left_on="k",
                                 right_on="rk")
    lone_l = l[~l.li.isin(inner.li)]
    lone_r = r[~r.ri.isin(inner.ri)]
    if jt == JoinType.LEFT_SEMI:
        out = l[l.li.isin(inner.li)]
    elif jt == JoinType.LEFT_ANTI:
        out = lone_l
    else:
        out = [inner]
        if jt in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER):
            out.append(lone_l)
        if jt in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
            out.append(lone_r)
        out = pd.concat(out, ignore_index=True)
    cols = ["k", "a"] if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI) \
        else ["k", "a", "rk", "b"]
    return out[cols].reset_index(drop=True)


def _frame(batches):
    return pd.concat([b.to_pandas() for b in batches], ignore_index=True)


def _same(want, got, label=""):
    """The same rows: every column as float64 (a null, whatever the
    engine or pandas calls it, as NaN), in one order."""
    assert list(want.columns) == list(got.columns), label
    w, g = (f.astype("float64").sort_values(
        list(f.columns), ignore_index=True) for f in (want, got))
    pd.testing.assert_frame_equal(w, g, obj=label)


@pytest.mark.parametrize("n", [1, 2, 4])
@pytest.mark.parametrize("jt", TYPES, ids=lambda t: t.value)
def test_partitionwise_equals_whole_build_and_pandas(jt, n, monkeypatch):
    left, right = _tables(3)
    join = _join(jt, left, right, n)
    assert join.co_partitions() == n
    assert join.output_partition_count() == n
    parts = [list(it) for it in join.execute_partitions()]
    assert len(parts) == n
    got = _frame([b for p in parts for b in p])
    whole = _join(jt, left, right, n)
    monkeypatch.setattr(whole, "co_partitions", lambda: None)
    assert whole.output_partition_count() == 1
    _same(_frame(whole.execute_columnar()), got, "whole")
    _same(_pandas_join(jt, left, right), got, "pandas")
    if n > 1 and jt != JoinType.LEFT_ANTI:
        # hash partitions are key-disjoint: no key in two partitions
        keys = [set(_frame(p).k.dropna()) for p in parts if p]
        assert sum(map(len, keys)) == len(set().union(*keys))


def test_other_shapes_keep_the_whole_build():
    left, right = _tables(4)
    lx = ShuffleExchangeExec(HashPartitioning([col("k")], 2),
                             _source(left, LEFT, "k", 2))
    rx4 = ShuffleExchangeExec(HashPartitioning([col("rk")], 4),
                              _source(right, RIGHT, "rk", 4))
    # unequal counts; no exchange; an exchange on another column
    assert HashJoinExec(JoinType.INNER, [col("k")], [col("rk")],
                        lx, rx4).co_partitions() is None
    assert HashJoinExec(JoinType.INNER, [col("k")], [col("rk")], lx,
                        _source(right, RIGHT, "rk", 2)
                        ).co_partitions() is None
    ra = ShuffleExchangeExec(HashPartitioning([col("b")], 2),
                             _source(right, RIGHT, "rk", 2))
    join = HashJoinExec(JoinType.INNER, [col("k")], [col("rk")], lx, ra)
    assert join.co_partitions() is None
    assert join.output_partition_count() == 1
    _same(_pandas_join(JoinType.INNER, left, right),
          _frame(join.execute_columnar()), "whole")


# ---- four chips ----------------------------------------------------------
@pytest.fixture(scope="module")
def mesh4():
    assert len(jax.devices()) >= 4
    return PM.make_mesh(4)


def _conf(**kw):
    return C.RapidsConf(dict({
        "spark.rapids.sql.variableFloatAgg.enabled": True,
        "spark.rapids.sql.incompatibleOps.enabled": True,
        "spark.rapids.sql.test.enabled": True}, **kw))


def _planned_join(left, right, conf, parts=4, broadcast=False):
    plan = N.CpuHashJoin(
        JoinType.INNER, [col("k")], [col("rk")],
        N.CpuSource.from_pandas(left.dropna(), num_partitions=parts),
        N.CpuSource.from_pandas(right.dropna(), num_partitions=parts),
        broadcast=broadcast)
    return accelerate(plan, conf)


def test_the_share_test_four_chips_are_the_one_partition_answer(mesh4):
    """Partition p is uploaded to chip p, exchanged onto chip p and
    joined there: every array of join partition p lies on chip p alone,
    the spans name four devices, nothing is moved, and the four chips'
    outputs, concatenated, are the one-partition answer row for row."""
    from spark_rapids_tpu.utils import profile as P
    left, right = _tables(5, rows=400)
    conf = _conf(**{"spark.rapids.sql.profile.enabled": True})
    CK.reset_cross_chip_moves()
    with PM.active_mesh(mesh4):
        join = _planned_join(left, right, conf)
        assert isinstance(join, HashJoinExec)
        assert join.output_partition_count() == 4
        for p, part in enumerate(join.children[0].child.partitions):
            assert all(c.data.devices() == {mesh4.devices[p]}
                       for b in part for c in b.columns)
        with C.session(conf):
            owner = P.begin_query(conf, join)
            parts = [list(it) for it in join.execute_partitions()]
            P.end_query(owner, join)
    assert CK.cross_chip_moves() == 0
    for p, part in enumerate(parts):
        assert part, p
        for b in part:
            for c in b.columns:
                assert c.data.devices() == {mesh4.devices[p]}, (p, c)
    prof = P.last_profile()
    for name in ("join-build", "join-probe"):
        spans = [s for s in prof.spans if s.name == name]
        assert sorted(s.args["partition"] for s in spans) == [0, 1, 2, 3]
        assert sorted(s.args["device"] for s in spans) == \
            [d.id for d in mesh4.devices.flat]
    one = _planned_join(left, right, _conf(), parts=1)
    want = _frame(one.execute_columnar())
    got = _frame([b for part in parts for b in part])
    order = ["a", "b"]
    pd.testing.assert_frame_equal(
        got.sort_values(order, ignore_index=True),
        want.sort_values(order, ignore_index=True))


@pytest.fixture(scope="module")
def tpch_tables():
    from spark_rapids_tpu.models.tpch_data import gen_tables
    return gen_tables(np.random.default_rng(7), 3000)


@pytest.mark.parametrize("query,sites", [
    (3, {"topn-merge": 1}),
    (5, {"exchange-map": 1}),
])
def test_tpch_on_four_chips_moves_only_at_single_partition_points(
        tpch_tables, mesh4, query, sites):
    """q3 and q5 (a deeper join chain) through accelerate() + collect()
    with one partition a chip: the CPU engine's answer, every hash
    exchange on the mesh lane, and exactly the moves the plan's
    single-partition points account for (q3: the top-10 merge; q5: the
    range exchange under its global sort)."""
    from spark_rapids_tpu.models.tpch_bench import run_query
    expected = run_query(query, tpch_tables, engine="cpu")
    ShuffleExchangeExec._MESH_SHARD_DEVICES = []
    CK.reset_cross_chip_moves()
    with PM.active_mesh(mesh4):
        got = run_query(query, tpch_tables, engine="tpu",
                        num_partitions=4)
    compare_frames(expected, got, f"q{query}-mesh4")
    assert ShuffleExchangeExec._MESH_SHARD_DEVICES
    assert all(ids == [0, 1, 2, 3]
               for ids in ShuffleExchangeExec._MESH_SHARD_DEVICES)
    moved = {k: n for k, (n, _) in CK.cross_chip_move_sites().items()}
    if query == 3:
        assert moved == sites       # ten candidates a chip, every run
    # q5's one group at this scale lies on one chip: nothing to move
    assert all(moved[k] <= sites.get(k, 0) for k in moved), moved
    assert CK.cross_chip_moves() == sum(moved.values())


def test_a_join_that_is_not_co_partitioned_gathers_through_the_counted_move(
        mesh4):
    """A broadcast join under the mesh: its build side comes to one
    chip through the counted move and its probe side follows."""
    left, right = _tables(6, rows=400)
    conf = _conf()
    CK.reset_cross_chip_moves()
    with PM.active_mesh(mesh4):
        plan = _planned_join(left, right, conf, broadcast=True)
        assert plan.output_partition_count() == 1
        got = collect(plan, conf)
    want = _pandas_join(JoinType.INNER, left.dropna(), right.dropna())
    _same(want, got, "broadcast")
    sites = CK.cross_chip_move_sites()
    assert set(sites) == {"broadcast", "join-probe"}
    assert all(n == 1 and nbytes > 0 for n, nbytes in sites.values())
