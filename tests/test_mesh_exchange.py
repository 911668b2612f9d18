"""Planner-routed mesh (ICI all-to-all) shuffle tests.

The accelerated exchange lane must be reachable from accelerate(), not
just from unit harnesses: a TPC-H join+groupby query planned normally,
with a mesh active, must route its hash exchanges through the collective
and still match the CPU golden engine (reference analog: UCX-inside-the-shuffle-manager,
RapidsShuffleInternalManager.scala:199)."""
import jax
import numpy as np
import pandas as pd
import pytest

from parity import compare_frames
from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.basic import LocalBatchSource
from spark_rapids_tpu.exprs.base import col
from spark_rapids_tpu.parallel.mesh import active_mesh, make_mesh
from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
from spark_rapids_tpu.shuffle.partitioning import HashPartitioning


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest must force 8 cpu devices"
    return make_mesh(8)


def _source(rng, n_parts=4, rows=200):
    schema = T.Schema.of(("k", T.INT64), ("v", T.FLOAT64),
                         ("s", T.STRING))
    parts = []
    for p in range(n_parts):
        parts.append([ColumnarBatch.from_numpy({
            "k": rng.integers(0, 50, rows).astype(np.int64),
            "v": rng.normal(size=rows),
            "s": np.array([f"p{p}r{i}" for i in range(rows)],
                          dtype=object),
        }, schema)])
    return LocalBatchSource(parts, schema=schema)


def test_exchange_exec_mesh_vs_local_lane(mesh8, rng):
    """The same ShuffleExchangeExec produces the same row-sets per
    partition through the mesh collective as through the local lane."""
    src = _source(rng)
    local = ShuffleExchangeExec(
        HashPartitioning([col("k")], 8), src)
    local_parts = [pd.concat([b.to_pandas() for b in it],
                             ignore_index=True)
                   for it in local.execute_partitions()]

    ShuffleExchangeExec._MESH_EXCHANGES_RUN = 0
    with active_mesh(mesh8):
        meshed = ShuffleExchangeExec(
            HashPartitioning([col("k")], 8), _source(
                np.random.default_rng(42)))
        mesh_parts = [pd.concat([b.to_pandas() for b in it],
                                ignore_index=True)
                      for it in meshed.execute_partitions()]
    assert ShuffleExchangeExec._MESH_EXCHANGES_RUN == 1
    assert len(local_parts) == len(mesh_parts) == 8
    for p, (lp, mp) in enumerate(zip(local_parts, mesh_parts)):
        compare_frames(lp, mp, f"part{p}")


def test_mesh_lane_shards_on_every_device_and_partition_d_stays_on_chip_d(
        mesh8):
    """The exchange's output holds a shard on every mesh device, and
    partition d is chip d's own shard of it: every array of it is a
    single-device array on chip d (on real chips a Mosaic kernel fed an
    array still spread over the mesh cannot be partitioned: four v5e
    chips, PR 25), and nothing came home to the first chip."""
    from spark_rapids_tpu.utils import checks as CK
    ShuffleExchangeExec._MESH_SHARD_DEVICES = []
    chips = list(mesh8.devices.flat)
    with active_mesh(mesh8):
        meshed = ShuffleExchangeExec(
            HashPartitioning([col("k")], 8), _source(
                np.random.default_rng(42), n_parts=8))
        parts = [list(it) for it in meshed.execute_partitions()]
        # a child with one partition a chip, already there: stacked
        # where it lies, so no byte crosses chips but in the all-to-all
        CK.reset_cross_chip_moves()
        again = ShuffleExchangeExec(
            HashPartitioning([col("v")], 8),
            LocalBatchSource(parts, parts[0][0].schema))
        parts2 = [list(it) for it in again.execute_partitions()]
        assert CK.cross_chip_moves() == 0
    assert ShuffleExchangeExec._MESH_SHARD_DEVICES == 2 * [list(range(8))]
    assert sum(b.num_rows for p in parts for b in p) == \
        sum(b.num_rows for p in parts2 for b in p) == 8 * 200
    for got in (parts, parts2):
        assert sum(bool(p) for p in got) > 1
        for d, part in enumerate(got):
            for b in part:
                for c in b.columns:
                    for a in (c.data, c.validity, c.lengths):
                        assert a is None or a.devices() == {chips[d]}, \
                            (d, a.sharding)


def test_mesh_lane_declines_without_mesh(rng):
    ex = ShuffleExchangeExec(HashPartitioning([col("k")], 8),
                             _source(rng))
    assert ex._mesh_routable() is None


def test_mesh_lane_declines_on_partition_mismatch(mesh8, rng):
    with active_mesh(mesh8):
        ex = ShuffleExchangeExec(HashPartitioning([col("k")], 4),
                                 _source(rng))
        assert ex._mesh_routable() is None


def test_mesh_lane_conf_off(mesh8, rng):
    conf = C.RapidsConf({"spark.rapids.shuffle.meshExchange.enabled":
                         False})
    with C.session(conf), active_mesh(mesh8):
        ex = ShuffleExchangeExec(HashPartitioning([col("k")], 8),
                                 _source(rng))
        assert ex._mesh_routable() is None


@pytest.fixture(scope="module")
def tpch_tables():
    from spark_rapids_tpu.models.tpch_data import gen_tables
    return gen_tables(np.random.default_rng(7), 3000)


@pytest.mark.parametrize("query", [3, 5])
def test_tpch_mesh_exchange_parity(tpch_tables, mesh8, query):
    """End-to-end: q3/q5 planned via accelerate() with an active mesh
    executes its hash exchanges over the 8-device mesh with parity vs
    the CPU golden engine."""
    from spark_rapids_tpu.models.tpch_bench import run_query
    expected = run_query(query, tpch_tables, engine="cpu")
    ShuffleExchangeExec._MESH_EXCHANGES_RUN = 0
    with active_mesh(mesh8):
        got = run_query(query, tpch_tables, engine="tpu")
    assert ShuffleExchangeExec._MESH_EXCHANGES_RUN > 0, \
        "no exchange actually took the mesh collective lane"
    compare_frames(expected, got, f"q{query}-mesh")


def test_oversized_single_batch_shards_across_mesh(mesh8):
    """SURVEY §5 long-context analog: ONE batch beyond the per-chip
    budget is split over the mesh devices before the all-to-all, and
    the exchanged result stays exact (the planner's half and the mesh's
    half together)."""
    import pandas as pd
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exprs.base import col
    from spark_rapids_tpu.plan.transitions import batch_from_df
    from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
    from spark_rapids_tpu.parallel.mesh import active_mesh

    rng = np.random.default_rng(33)
    rows = 4000
    df = pd.DataFrame({
        "k": rng.integers(0, 500, rows).astype(np.int64),
        "v": rng.uniform(0, 1, rows)})
    schema_src = batch_from_df(df, None) if False else None
    from spark_rapids_tpu.plan.nodes import CpuSource
    schema = CpuSource.from_pandas(df).output_schema()
    big = batch_from_df(df, schema)  # ONE oversized batch
    src = LocalBatchSource([[big]], schema)
    conf = C.RapidsConf({"spark.rapids.tpu.batchMaxRows": 512})
    before = ShuffleExchangeExec._OVERSIZED_SPLITS
    with C.session(conf), active_mesh(mesh8):
        ex = ShuffleExchangeExec(HashPartitioning([col("k")], 8), src)
        outs = [b for it in ex.execute_partitions() for b in it]
    assert ShuffleExchangeExec._OVERSIZED_SPLITS > before, \
        "oversized batch was not sharded"
    got = pd.concat([b.to_pandas() for b in outs], ignore_index=True)
    assert len(got) == rows
    assert int(got["k"].sum()) == int(df["k"].sum())
    # partition routing is still murmur3-exact after the split
    from spark_rapids_tpu.ops.murmur3 import partition_ids
    import jax.numpy as jnp
    for p, b in enumerate(outs):
        if b.num_rows == 0:
            continue
        pb = b.to_pandas()
        import numpy as _np
        kcol = big.column("k")
        # recompute expected partition of each routed key via the engine
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        chk = ColumnarBatch.from_pandas(pb[["k"]])
        pids = _np.asarray(partition_ids([chk.column("k")], 8)
                           )[:chk.num_rows]
        assert (pids == p).all()
