"""The cell `sf025-q3-mesh` as `BENCHMARK.json` has it: found by name,
rehearsed on four virtual devices (correct; a shard on four chips;
counts only; the float32 control and the all-to-all left out both not
correct), and its new readers on spans and device operations written
out by hand."""
import argparse
import json

import pandas as pd
import pytest

from benchmark import manifest as MF
from benchmark import run as RUN
from benchmark.reduce import exchange_least_bytes as XB
from benchmark.reduce import ici_peaks as ICI

CELL = "sf025-q3-mesh"
BESIDE = "sf025-q3-join"
MANIFEST = MF.load()
NEW = ("exchange_shard_chips", "collective_ms", "collective_device_ms",
       "collective_ici_roofline_pct", "chip_busy_balance_pct",
       "cross_chip_moves")
SHARED = ("accelerate_ms", "gc_ms_per_query", "first_query_s",
          "compile_requests", "host_syncs", "hbm_roofline_pct",
          "device_idle_pct", "peak_hbm_gb")
MS = 1_000_000


def reader(name):
    return MF.module_at("layer_metrics", name + ".py").read


def args_for(**kw):
    base = dict(workload=CELL, seed=2 ** 31 + 35, seconds=0.5, trace=0,
                rehearse=True, control=None, manifest=None)
    base.update(kw)
    return argparse.Namespace(**base)


# ---- the entries ---------------------------------------------------------
def test_the_cell_is_as_the_issue_names_it():
    cell = MF.Cell(MANIFEST, CELL)
    assert cell.entry["config"] == "tpch-sf025-4chip-mesh"
    assert cell.entry["traffic"] == "q3-closed-1" and cell.chips == 4
    assert cell.queries == [3]
    # the benchmark's one four-chip cell, and its last
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["chips"] == 4] == [CELL]
    assert MANIFEST["workloads"][-1]["name"] == CELL
    assert MANIFEST["configs"][-1]["name"] == "tpch-sf025-4chip-mesh"
    assert MANIFEST["configs"][-1]["reduced"] == ["scale"]
    # sf025-q3-join's deployment word for word, on four chips
    base = MF.Cell(MANIFEST, BESIDE).config
    for key in ("suite", "generator", "queries", "sources", "scale",
                "rehearse_scale", "conf", "reduced", "assumed"):
        assert cell.config[key] == base[key], key
    assert cell.config["partitions"] == cell.config["mesh_chips"] == 4
    assert "meshExchange" not in json.dumps(cell.config["conf"])
    for key, text in base["guarantees"].items():
        assert cell.config["guarantees"][key].startswith(text), key
    placement = cell.config["guarantees"]["placement"]
    assert "one partition a chip" in placement
    assert "all-to-all" in placement and "counted move" in placement
    assert "a quarter" in cell.config["guarantees"]["size"]
    assert "4 executors x 1 chip" in cell.config["guarantees"]["deployment"]
    assert cell.config["source"] != base["source"]
    assert len(cell.config["source"]) <= 200
    assert cell.limits["float_rel_err"] == 1e-10
    assert cell.limits["control"] == "float32"
    lower, upper = (cell.limits["set_from"][k]["reading"]
                    for k in ("lower", "upper"))
    assert lower < 1e-10 / 50 and upper > 1e-10 * 50
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {"setup_s", "query_ms_p50", "input_rows_per_s"}
    layers = {m["name"] for m in cell.metrics("per_layer")}
    assert layers == set(NEW) | set(SHARED)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"]][-6:] == list(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL, name
    assert by_name["chip_busy_balance_pct"]["moves"] == "input_rows_per_s"
    assert by_name["chip_busy_balance_pct"]["layer"] == "device"
    assert by_name["collective_ici_roofline_pct"]["unit"] == "%"
    assert {by_name[n]["source"] for n in
            ("exchange_shard_chips", "cross_chip_moves")} == \
        {"program_counter"}


# ---- rehearsed on four virtual devices -----------------------------------
def test_rehearsal_is_correct_with_one_partition_a_chip():
    result = RUN.run_cell(args_for())
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["compared"]["float_rel_err"]["value"] < 1e-13
    assert result["rehearsed_on"] == "cpu" and result["metrics"] == {}
    assert result["device"]["count"] >= 4


def test_traced_rehearsal_reports_counts_only():
    result = RUN.run_cell(args_for(trace=1))
    assert result["correct"] is True
    counts = {m["name"] for m in MF.Cell(MANIFEST, CELL).metrics("per_layer")
              if m["source"] == "program_counter"}
    assert set(result["metrics"]) <= counts
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # every hash exchange held a shard on four chips
    assert got["exchange_shard_chips"] == 4
    # the plan's one single-partition point: the top-10 merge
    assert got["cross_chip_moves"] == 1
    assert got["host_syncs"] > 0 and got["compile_requests"] > 0


def test_the_float32_control_is_not_correct():
    result = RUN.run_cell(args_for(control="float32"))
    assert result["correct"] is False
    row = result["compared"]["float_rel_err"]
    assert row["value"] > row["limit"]
    assert all(result["compared"][k]["value"] == 0
               for k in result["compared"] if k != "float_rel_err")


def test_the_all_to_all_left_out_reads_not_correct(monkeypatch):
    """The exchange between chips left out: a chip keeps only the block
    it would have sent itself.  Since the join runs partition by
    partition this breaks the answer whichever rows stay."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.exec.base import clear_kernel_cache

    def no_exchange(x, axis_name, split_axis, concat_axis, **kw):
        mine = jax.lax.axis_index(axis_name)
        block = jnp.arange(x.shape[0]).reshape(
            (-1,) + (1,) * (x.ndim - 1)) == mine
        return jnp.where(block, x, jnp.zeros_like(x))

    clear_kernel_cache()
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "all_to_all", no_exchange)
    try:
        result = RUN.run_cell(args_for(seed=21))
    finally:
        monkeypatch.undo()
        clear_kernel_cache()
        jax.clear_caches()
    assert result["correct"] is False, result["compared"]


# ---- the readers, by hand ------------------------------------------------
def mesh_spans(t0, scale=1, moves=1):
    """One q3's spans on the mesh lane, ms from `t0`: four exchanges,
    each a write, a collective and four reads; `moves` counted moves."""
    def span(name, lo, hi):
        return (name, t0 + lo * scale * MS, t0 + hi * scale * MS)
    out = [span("bench:accelerate", -30, 0), span("bench:collect", 0, 100)]
    for k, at in enumerate((2, 12, 30, 40)):
        out += [span("exec:exchange-write", at, at + 3),
                span("exec:exchange-collective", at + 3, at + 5 + k)]
        out += [span("exec:exchange-read", at + 6 + k, at + 7 + k)] * 4
    out += [span("exec:join-build", 20, 24), span("exec:join-probe", 24, 28)]
    out += [span("exec:to-one-chip", 90 + i, 90.5 + i)
            for i in range(moves)]
    return out


def test_collective_ms_sums_a_querys_collective_spans():
    a = mesh_spans(1_000 * MS)                  # 2 + 3 + 4 + 5 = 14 ms
    b = mesh_spans(2_000 * MS, scale=2)         # 28
    c = mesh_spans(4_000 * MS, scale=3)         # 42
    outside = [("exec:exchange-collective", 500 * MS, 600 * MS)]
    ctx = {"planes": {"devices": {}, "spans": a + b + c + outside}}
    assert reader("collective_ms")(ctx) == pytest.approx(28.0)
    # the parent's program: the lane's span was `shuffle:mesh-exchange`
    parent = [s for s in a if "collective" not in s[0]]
    assert reader("collective_ms")(
        {"planes": {"devices": {}, "spans": parent}}) is None
    assert reader("collective_ms")({"planes": {}, "trace": None}) is None


def test_cross_chip_moves_counts_a_querys_counted_moves():
    spans = (mesh_spans(1_000 * MS, moves=1)
             + mesh_spans(2_000 * MS, moves=1)
             + mesh_spans(3_000 * MS, moves=3))
    # one inside accelerate() is the query's too
    spans.append(("exec:to-one-chip", 2_990 * MS, 2_991 * MS))
    ctx = {"planes": {"devices": {}, "spans": spans}}
    assert reader("cross_chip_moves")(ctx) == 1
    # a program without the span (the parent) reads nothing
    bare = [s for s in spans if s[0] != "exec:to-one-chip"]
    assert reader("cross_chip_moves")(
        {"planes": {"devices": {}, "spans": bare}}) is None
    assert reader("cross_chip_moves")({"planes": {}}) is None


def four_chip_ctx():
    spans = [("bench:accelerate", 100 * MS, 200 * MS),
             ("bench:collect", 200 * MS, 1_000 * MS),
             ("bench:accelerate", 1_000 * MS, 1_100 * MS),
             ("bench:collect", 1_100 * MS, 2_100 * MS)]

    def chip(d):
        return [
            ("jit_mesh_count/%fusion s32[4] fusion kLoop",
             300 * MS, 310 * MS),
            ("jit_mesh_exchange/%all-to-all f64[4,65536] all-to-all",
             310 * MS, 340 * MS + d * MS),
            # an operation on the line beside the one that holds it: once
            ("jit_mesh_exchange/%scatter f64[4,65536] scatter",
             320 * MS, 330 * MS),
            ("jit_join_match/%sort u32[266240] sort",
             400 * MS, (500 + 100 * d) * MS),
            ("jit_mesh_exchange/%all-to-all f64[4,65536] all-to-all",
             1_200 * MS, 1_220 * MS),
        ]
    devices = {f"/device:TPU:{d}": chip(d) for d in range(4)}
    busy = {f"/device:TPU:{d}": (160 + 101 * d) / 1e3 for d in range(4)}
    return {"planes": {"devices": devices, "spans": spans},
            "trace": {"queries": 2, "traced": [3, 3],
                      "busy_s_by_chip": busy}, "chips": 4}


def test_collective_device_ms_reads_the_busiest_chips_mesh_programs():
    ctx = four_chip_ctx()
    # chip 3 is busiest: 10 + (30 + 3) + 20 = 63 ms over two queries
    assert reader("collective_device_ms")(ctx) == pytest.approx(31.5)
    ctx["planes"]["devices"] = {
        k: [op for op in v if not op[0].startswith("jit_mesh_")]
        for k, v in ctx["planes"]["devices"].items()}
    assert reader("collective_device_ms")(ctx) is None
    assert reader("collective_device_ms")({"planes": {}, "trace": {}}) is None


def test_chip_busy_balance_is_the_least_busy_chip_over_the_busiest():
    ctx = four_chip_ctx()
    assert reader("chip_busy_balance_pct")(ctx) == \
        pytest.approx(100 * 160 / 463)
    # the parent: chips 1-3 run the collective alone
    ctx["trace"]["busy_s_by_chip"] = {
        "/device:TPU:0": 2.4, "/device:TPU:1": 0.06,
        "/device:TPU:2": 0.07, "/device:TPU:3": 0.06}
    assert reader("chip_busy_balance_pct")(ctx) == pytest.approx(2.5)
    # one chip, or no trace: nothing
    ctx["trace"]["busy_s_by_chip"] = {"/device:TPU:0": 2.4}
    assert reader("chip_busy_balance_pct")(ctx) is None
    assert reader("chip_busy_balance_pct")({"trace": {}}) is None
    assert reader("chip_busy_balance_pct")({"trace": None}) is None


def tiny_tables():
    """Hand-made: 4 customers (2 BUILDING), 6 orders (4 before the
    date, 3 of them of BUILDING customers), 8 lines (5 shipped after)."""
    d = XB._days("1995-03-15")
    return {
        "customer": pd.DataFrame({
            "c_custkey": pd.array([0, 1, 2, 3], "int64"),
            "c_mktsegment": ["BUILDING", "AUTOMOBILE", "BUILDING",
                             "MACHINERY"]}),
        "orders": pd.DataFrame({
            "o_orderkey": pd.array(range(6), "int64"),
            "o_custkey": pd.array([0, 0, 1, 2, 2, 3], "int64"),
            "o_orderdate": pd.array([d - 5, d - 1, d - 2, d - 9, d, d + 3],
                                    "int32"),
            "o_shippriority": pd.array([0] * 6, "int32")}),
        "lineitem": pd.DataFrame({
            "l_orderkey": pd.array([0, 0, 1, 2, 3, 3, 4, 5], "int64"),
            "l_shipdate": pd.array(
                [d + 1, d - 1, d + 2, d + 9, d, d + 4, d + 5, d - 3],
                "int32"),
            "l_extendedprice": [1.0] * 8, "l_discount": [0.0] * 8}),
    }


def test_exchange_least_bytes_by_hand():
    t = tiny_tables()
    assert XB.tpch_q3(t) == [
        ("customer", 2, 8),                 # c_custkey
        ("orders", 4, 8 + 8 + 4 + 4),       # keys, date, priority
        ("customer-orders", 3, 8 + 4 + 4),  # orders 0, 1, 3
        ("lineitem", 5, 8 + 8 + 8)]         # key, price, discount
    by_hand = 2 * 8 + 4 * 24 + 3 * 16 + 5 * 24            # 280 bytes
    assert XB.query_cross_chip_bytes("tpch", 3, t, 4) == by_hand * 3 / 4
    assert XB.query_cross_chip_bytes("tpch", 3, t, 2) == by_hand / 2
    assert ICI.ici_peak_of("TPU v5 lite")["ici_bytes_per_s"] == 200e9
    with pytest.raises(KeyError):
        ICI.ici_peak_of("cpu")


def test_ici_roofline_is_least_bytes_over_rate_over_the_mesh_programs_time(
        monkeypatch):
    ctx = four_chip_ctx()
    cell = MF.Cell(MANIFEST, CELL)
    ctx.update(cell=cell, config=cell.config,
               device={"kind": "TPU v5 lite"},
               queries={3: {"rows": 1_000_000, "least_bytes": 1}})
    # 12 bytes a source row must cross, by decree: 2 queries x 12 MB
    monkeypatch.setattr(XB, "per_source_row", lambda *a, **k: 12.0)
    busy_s = 0.063                          # chip 3's `jit_mesh_` time
    want = 100 * 24e6 / (200e9 * 4) / busy_s
    assert reader("collective_ici_roofline_pct")(ctx) == pytest.approx(want)
    assert want < 1
    # one chip, no trace, no mesh program: nothing
    assert reader("collective_ici_roofline_pct")(dict(ctx, chips=1)) is None
    assert reader("collective_ici_roofline_pct")(
        dict(ctx, trace={})) is None
    ctx["planes"] = {"devices": {}, "spans": ctx["planes"]["spans"]}
    assert reader("collective_ici_roofline_pct")(ctx) is None


def test_the_sampled_share_is_the_generators_own():
    """`per_source_row` reads the filters' shares from the generator at
    a sample scale: the same to a few per cent at another seed's
    tables, whatever their size."""
    import importlib
    cell = MF.Cell(MANIFEST, CELL)
    reads = cell.references[3].READS
    sampled = XB.per_source_row(cell.config, 3, reads, 4,
                                sample_scale=60_000)
    tables = importlib.import_module(cell.config["generator"]).generate(
        77, 40_000, list(reads))
    exact = XB.query_cross_chip_bytes("tpch", 3, tables, 4) / sum(
        len(tables[t]) for t in reads)
    assert sampled == pytest.approx(exact, rel=0.05)
    assert 5 < sampled < 20                 # bytes a source row
