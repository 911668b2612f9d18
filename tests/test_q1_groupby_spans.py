"""What a profiled TPC-H q1 says of itself: the string upload's span
(and that no `str` column is handled a value at a time on its way to
the device), the group-by merge's capacity and rounds, and the lane a FLOAT64
measure grouped on STRING keys takes under the default conf (ROADMAP
Queue 2, first list #3: the test to turn when a fast lane takes it).
"""
from __future__ import annotations

import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar.vector import bucket_capacity
from spark_rapids_tpu.utils import checks as CK
from spark_rapids_tpu.utils import profile as P

SCALE = 20_000
PARTITIONS = 2
CHUNK_ROWS = 4096       # 10,000-row partitions: two full chunks and a tail
#: the conf the reference's TPC harness runs, with no lane switch set
DEFAULTS = {"spark.rapids.sql.variableFloatAgg.enabled": True,
            "spark.rapids.sql.incompatibleOps.enabled": True,
            "spark.rapids.sql.test.enabled": True}


def _run(query, tables, profile):
    """(answer, profile or None, {site: host syncs} of accelerate +
    collect)."""
    from spark_rapids_tpu.models.tpch_data import sources
    from spark_rapids_tpu.models.tpch_queries import QUERIES
    from spark_rapids_tpu.plan.overrides import accelerate, collect
    conf = C.RapidsConf(dict(
        DEFAULTS, **{"spark.rapids.sql.profile.enabled": profile,
                     "spark.rapids.tpu.batchMaxRows": CHUNK_ROWS}))
    P.clear_history()
    before = CK.host_sync_sites()
    plan = accelerate(QUERIES[query](sources(tables, PARTITIONS), None),
                      conf)
    answer = collect(plan, conf)
    syncs = {site: n - before.get(site, 0)
             for site, n in CK.host_sync_sites().items()
             if n > before.get(site, 0)}
    return answer, P.last_profile(), syncs


@pytest.fixture(scope="module")
def tables():
    from benchmark.gen import tpch
    return tpch.generate(2 ** 31 + 33, SCALE,
                         ["lineitem", "orders", "customer"])


@pytest.fixture(scope="module")
def q1(tables):
    """q1 unprofiled (so no span holds a compile), then profiled."""
    plain, none, plain_syncs = _run(1, tables, False)
    assert none is None and len(plain) == 4
    answer, prof, syncs = _run(1, tables, True)
    assert answer.equals(plain)
    from spark_rapids_tpu.plan.overrides import ExecutionPlanCapture
    PROFILED_PLAN.append(ExecutionPlanCapture.last_plan)
    return prof, syncs, plain_syncs


#: the plan of `q1`'s profiled run (later runs replace the capture's)
PROFILED_PLAN: list = []


def _named(prof, name):
    return [s for s in prof.spans if f"{s.cat}:{s.name}" == name]


def test_q1_opens_one_string_upload_span_a_partition(q1, tables):
    prof, _, _ = q1
    puts = _named(prof, "exec:upload-put")
    strings = _named(prof, f"exec:{P.SPAN_UPLOAD_STRINGS}")
    assert len(strings) == len(puts) == PARTITIONS
    by_id = {s.sid: s for s in puts}
    for s in strings:
        put = by_id[s.parent_id]            # inside the partition's put
        assert put.t0 <= s.t0 and s.t0 + s.dur_ns <= put.t0 + put.dur_ns
        assert set(s.args) == {"columns", "chunks", "rows", "device_bytes",
                               "transfers", "per_value"}
        assert s.args["columns"] == 2       # l_returnflag, l_linestatus
        assert s.args["chunks"] == put.args["chunks"] == 3
        assert s.args["rows"] == put.args["rows"]
        # byte matrices + validity + lengths a column, once for the
        # run's two full chunks and once for its tail; q1's five
        # fixed-width columns are the rest of the put's arrays
        tail = s.args["rows"] % CHUNK_ROWS > 0
        assert tail and s.args["transfers"] == 3 * s.args["columns"] * (
            1 + tail)
        assert s.args["per_value"] == 0     # no Python call a value
        assert 0 < s.args["transfers"] < put.args["transfers"]
        assert 0 < s.args["device_bytes"] < put.args["device_bytes"]
    assert sum(s.args["rows"] for s in strings) == len(tables["lineitem"])


@pytest.mark.parametrize("query", [1, 3])
def test_no_str_column_takes_the_per_value_path(query, tables, monkeypatch):
    """q1's two keys and q3's `c_mktsegment` reach the device from their
    Arrow buffers: with the per-value encoder taken away both still
    answer, and as they answered with it."""
    from spark_rapids_tpu.columnar import batch as CB
    from spark_rapids_tpu.columnar import vector as CV
    expected, _, _ = _run(query, tables, False)

    def per_value(*_a, **_k):
        raise AssertionError("a string column went value by value")
    monkeypatch.setattr(CV, "_strings_from_host", per_value)
    monkeypatch.setattr(CB, "_strings_from_host", per_value)
    answer, _, _ = _run(query, tables, False)
    assert len(answer) and answer.equals(expected)


def test_an_arrow_backed_frame_builds_no_object_array(tables):
    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.models.tpch_data import SCHEMAS
    from spark_rapids_tpu.plan.transitions import host_columns_from_df
    part = tables["lineitem"].iloc[7:5000]      # a partition's slice
    data, validity = host_columns_from_df(part, SCHEMAS["lineitem"])
    strings = [f.name for f in SCHEMAS["lineitem"].fields
               if f.dtype.is_string]
    assert {"l_returnflag", "l_linestatus"} <= set(strings)
    for name in strings:
        assert isinstance(data[name], pa.LargeStringArray)
        assert not isinstance(data[name], np.ndarray)
        assert validity[name].dtype == bool and validity[name].all()
    assert not any(isinstance(a, np.ndarray) and a.dtype == object
                   for a in data.values())


def test_a_source_without_string_columns_opens_none(tables):
    _run(6, tables, False)
    _, prof, _ = _run(6, tables, True)
    assert len(_named(prof, "exec:upload-put")) == PARTITIONS
    assert _named(prof, f"exec:{P.SPAN_UPLOAD_STRINGS}") == []


def test_the_merge_span_says_what_the_kernel_was_given(q1):
    prof, syncs, _ = q1
    merges = _named(prof, "exec:groupby-merge")
    assert merges
    for s in merges:
        assert set(s.args) == {"lane", "partials", "groups",
                               "capacity_rows", "rounds"}
        # one partial needs no merge; more are concatenated and merged
        # in one kernel call, at no less than a slot a partial
        if s.args["partials"] == 1:
            assert s.args["rounds"] == s.args["capacity_rows"] == 0
        else:
            assert s.args["rounds"] == 1
            assert s.args["partials"] <= s.args["capacity_rows"]
    # the partial aggregate merges a partition's three chunks (12,288
    # slots, past one batch of padding: their counts are asked for once
    # a partition and the concat is tight: four groups a chunk); the
    # final one is handed each reduce partition's slices as one batch
    three = [s for s in merges if s.args["partials"] == 3]
    assert len(three) == PARTITIONS == syncs["agg.merge"]
    assert {s.args["capacity_rows"] for s in three} == \
        {bucket_capacity(3 * 4)}


@pytest.mark.parametrize("chunk_rows,asks,capacity", [
    # 3 partials of 256 slots: 768 -> 1,024, within one batch (4,096)
    (4096, 0, 1024),
    # 10 partials of 256 slots: 2,560 -> 4,096, past one batch (1,024):
    # one stacked read a partition, then the bucket of the 40 rows
    (1024, PARTITIONS, bucket_capacity(10 * 4)),
], ids=["within-a-batch-of-padding", "past-it"])
def test_the_merge_asks_for_its_partials_counts_past_one_batch_of_padding(
        tables, monkeypatch, chunk_rows, asks, capacity):
    """Compacted partials keep their group counts on the device, so
    their lazy concat has the bucketed SUM of their capacities (q1 at
    SF1: 2^20 slots for 184 rows); `_merge_partials` follows the
    build side's rule (`rows_made_known`)."""
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    monkeypatch.setattr(HashAggregateExec, "COMPACT_GROUPS_CAP", 256)
    monkeypatch.setitem(globals(), "CHUNK_ROWS", chunk_rows)
    plain, _, plain_syncs = _run(1, tables, False)
    answer, prof, syncs = _run(1, tables, True)
    assert answer.equals(plain) and len(plain) == 4
    assert plain_syncs.get("agg.merge", 0) == asks == \
        syncs.get("agg.merge", 0)
    merges = [s for s in _named(prof, "exec:groupby-merge")
              if s.args["partials"] > 1]
    assert len(merges) == PARTITIONS
    assert {s.args["capacity_rows"] for s in merges} == {capacity}
    assert {s.args["rounds"] for s in merges} == {1}


def test_the_new_span_and_arguments_read_nothing_from_the_device(q1):
    """The query's own blocking reads are those of the unprofiled run;
    what a profiled run adds is `QueryProfile.build` resolving the
    operators' device-held metrics (`metrics.resolve`), as before."""
    _, syncs, plain_syncs = q1
    assert sum(plain_syncs.values()) > 0
    own = {k: v for k, v in syncs.items() if k != "metrics.resolve"}
    assert own == plain_syncs


def test_the_drain_reads_each_collision_flag_once(tables, monkeypatch):
    """Each grouped batch registers a deferred collision flag that rides
    on its batch AND on the query's registry, so the collect's drain is
    handed every flag twice; its `exec:Readback` span says so, and that
    `checks.verify` read each once, in its one host sync."""
    registered, original = [], CK.register

    def register(check):
        registered.append(check)
        return original(check)
    monkeypatch.setattr(CK, "register", register)
    _, prof, syncs = _run(1, tables, True)
    assert registered
    assert {c.origin.split("[")[0] for c in registered} == {"hashGroupby"}
    (drain,) = [s for s in _named(prof, "exec:Readback")
                if s.args["phase"] == "drain"]
    assert drain.args["checks_read"] == len(set(registered))
    assert drain.args["checks_given"] == 2 * drain.args["checks_read"]
    assert syncs["checks.verify"] == 1


def _nodes(plan, name, out=None):
    out = [] if out is None else out
    if type(plan).__name__ == name:
        out.append(plan)
    for c in getattr(plan, "children", []):
        _nodes(c, name, out)
    return out


def test_q1_takes_the_few_groups_body_under_the_default_conf(q1):
    """Eight FLOAT64 aggregates on two STRING keys: no dict, banded or
    MXU lane takes them (`_measure_types`, `_dict_plan`), in the update
    or in the merge; the grouped kernel does, and since every batch of
    q1 has four groups its few-groups body runs every time: no sort, and
    the FLOAT64 measures still summed in float64."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.utils import metrics as M
    prof, _, _ = q1
    updates = _named(prof, "exec:groupby-update")
    merges = _named(prof, "exec:groupby-merge")
    assert {s.args["phase"] for s in updates} == {"update", "merge"}
    assert {s.args["lane"] for s in updates} == {"few-or-sort"}
    assert {s.args["lane"] for s in merges} <= {"few-or-sort", None}
    assert "few-or-sort" in {s.args["lane"] for s in merges}
    # the counter: every batch either aggregate was handed (the partial
    # one's updates and merges, the final one's merges) took the few body
    aggs = _nodes(PROFILED_PLAN[-1], "HashAggregateExec")
    (partial,) = [a for a in aggs if a._pre_stage is not None]
    (final,) = [a for a in aggs if a._pre_stage is None]
    n_updates = sum(s.args["batches"] for s in updates
                    if s.args["phase"] == "update")
    # the partial aggregate's updates, a chunk each, and its merge of
    # them, once a partition
    assert n_updates == PARTITIONS * 3
    assert partial.metrics.value(M.NUM_FEW_GROUPS_OFFERED) == \
        n_updates + PARTITIONS
    for agg in (partial, final):
        offered = agg.metrics.value(M.NUM_FEW_GROUPS_OFFERED)
        assert offered > 0
        assert agg.metrics.value(M.NUM_FEW_GROUP_BATCHES) == offered
    # float64 by construction: the partial layout's sum columns
    sums = [f for f in partial.output_schema().fields
            if f.name.startswith(("sum_", "avg_")) and f.name.endswith("#0")]
    assert len(sums) == 7 and all(f.dtype == T.FLOAT64 for f in sums)
    assert T.FLOAT64.storage_dtype == jnp.float64
