"""The cell `sf025-q3-join` as `BENCHMARK.json` has it: found by name,
rehearsed on the CPU (correct; counts only; the float32 control not
correct), and its five readers on spans and device operations written
out by hand."""
import argparse

import pytest

from benchmark import manifest as MF
from benchmark import run as RUN
from benchmark.reduce import phases as PH
from benchmark.reduce import programs as PG

CELL = "sf025-q3-join"
MANIFEST = MF.load()
NEW = ("join_ms", "exchange_ms", "join_device_ms", "exchange_device_ms",
       "groupby_device_ms")
MS = 1_000_000


def reader(name):
    return MF.module_at("layer_metrics", name + ".py").read


def args_for(**kw):
    base = dict(workload=CELL, seed=2 ** 31 + 29, seconds=0.5, trace=0,
                rehearse=True, control=None, manifest=None)
    base.update(kw)
    return argparse.Namespace(**base)


# ---- the entries ---------------------------------------------------------
def test_the_cell_is_as_the_issue_names_it():
    cell = MF.Cell(MANIFEST, CELL)
    assert cell.entry["config"] == "tpch-sf025-1chip-defaults"
    assert cell.entry["traffic"] == "q3-closed-1" and cell.chips == 1
    assert cell.queries == [3]
    conf = cell.config["conf"]
    # the plugin's default lanes: neither switch is set
    assert not [k for k in conf if "Groupby" in k]
    assert conf == {k: v for k, v in MF.Cell(
        MANIFEST, "sf025-q6-scan").config["conf"].items()
        if "Groupby" not in k}
    base = MF.Cell(MANIFEST, "sf025-q6-scan").config
    for key in ("suite", "generator", "queries", "sources", "scale",
                "rehearse_scale", "partitions", "assumed", "reduced"):
        assert cell.config[key] == base[key], key
    assert cell.config["source"] != base["source"]
    assert cell.limits["float_rel_err"] == 1e-10
    assert cell.limits["control"] == "float32"
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {"setup_s", "query_ms_p50", "input_rows_per_s"}
    layers = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW) <= layers and "exchange_shard_chips" not in layers
    for m in MANIFEST["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "query_ms_p50"


# ---- rehearsed on the CPU ------------------------------------------------
def test_rehearsal_is_correct_under_the_default_lanes():
    result = RUN.run_cell(args_for())
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["compared"]["float_rel_err"]["value"] < 1e-13
    assert result["rehearsed_on"] == "cpu" and result["metrics"] == {}


def test_traced_rehearsal_reports_counts_only():
    result = RUN.run_cell(args_for(trace=1))
    assert result["correct"] is True
    counts = {m["name"] for m in MF.Cell(MANIFEST, CELL).metrics("per_layer")
              if m["source"] == "program_counter"}
    assert set(result["metrics"]) <= counts
    assert result["metrics"]["host_syncs"]["value"] > 0
    assert result["metrics"]["compile_requests"]["value"] > 0


def test_the_float32_control_is_not_correct():
    result = RUN.run_cell(args_for(control="float32"))
    assert result["correct"] is False
    row = result["compared"]["float_rel_err"]
    assert row["value"] > row["limit"]
    assert all(result["compared"][k]["value"] == 0
               for k in result["compared"] if k != "float_rel_err")


# ---- the five readers, by hand -------------------------------------------
def q3_spans(t0, scale=1):
    """One query's phase spans as a pull pipeline nests them, ms from
    `t0` (times `scale`): the group-by's update holds the second join,
    whose build holds an exchange that holds the first join; two
    exchange reads overlap the probe on a prefetch thread."""
    def span(name, lo, hi):
        return (name, t0 + lo * scale * MS, t0 + hi * scale * MS)
    return [
        span("bench:collect", 0, 100),
        span("exec:SortedTopNExec[p0]", 1, 99),
        span("exec:groupby-update", 2, 80),
        span("exec:join-build", 4, 50),             # join 2
        span("exec:exchange-write", 6, 40),
        span("exec:join-build", 8, 20),             # join 1, nested
        span("exec:join-probe", 20, 36),
        span("exec:exchange-read", 22, 30),         # inside join 1's probe
        span("exec:exchange-read", 42, 48),
        span("exec:join-probe", 50, 78),            # join 2
        span("exec:exchange-write", 52, 60),
        span("exec:exchange-read", 58, 66),         # overlaps the write
        span("exec:groupby-merge", 80, 90),
        span("exec:Readback", 92, 98),
    ]


def owned_by_hand():
    """Every instant to the phase span opened last:
    join      [4,6) [8,22) [30,36) [50,52) [66,78)      = 2+14+6+2+12 = 36
    exchange  [6,8) [22,30) [36,40) [42,48) [52,66)     = 2+8+4+6+14 = 34
    group-by  [2,4) [78,80) [80,90)                     = 2+2+10 = 14
    and [40,42), [48,50) fall back to the join's build  = +4 -> join 40"""
    return {"exec:join-": 40, "exec:exchange-": 34, "exec:groupby-": 14}


def test_phase_ownership_by_hand():
    spans = q3_spans(1_000 * MS)
    lo, hi = spans[0][1], spans[0][2]
    got = PH.owned_ns(spans, lo, hi)
    assert {k: v / MS for k, v in got.items()} == owned_by_hand()
    # never more than the query, however the spans nest and overlap
    assert sum(got.values()) <= hi - lo
    assert sum(e - s for n, s, e in spans
               if n.startswith(PH.FAMILIES)) > hi - lo


def test_host_readers_take_the_median_query_and_only_spans_inside_collect():
    a = q3_spans(1_000 * MS)
    b = q3_spans(2_000 * MS, scale=2)
    c = q3_spans(4_000 * MS, scale=3)
    outside = [("exec:join-probe", 500 * MS, 600 * MS),      # warm-up's
               ("exec:exchange-write", 3_900 * MS, 3_950 * MS)]
    ctx = {"planes": {"devices": {}, "spans": a + b + c + outside}}
    want = owned_by_hand()
    assert reader("join_ms")(ctx) == 2 * want["exec:join-"]
    assert reader("exchange_ms")(ctx) == 2 * want["exec:exchange-"]
    assert PH.family_ms(ctx, "exec:groupby-") == 2 * want["exec:groupby-"]


def test_a_trace_with_no_join_reads_nothing():
    q6 = [("bench:accelerate", 0, 30 * MS),
          ("exec:SourceUpload[s0]", 2 * MS, 28 * MS),
          ("bench:collect", 30 * MS, 90 * MS),
          ("exec:HashAggregateExec[p0]", 31 * MS, 80 * MS),
          ("exec:groupby-update", 32 * MS, 70 * MS),
          ("exec:groupby-merge", 70 * MS, 79 * MS),
          ("exec:Readback", 80 * MS, 89 * MS)]
    devices = {"/device:TPU:0": [
        ("jit_agg_reduce_update/%fusion f64[] fusion kCustom",
         40 * MS, 60 * MS),
        ("jit_upload_split/%slice f64[65536] slice", 10 * MS, 11 * MS)]}
    ctx = {"planes": {"devices": devices, "spans": q6},
           "trace": {"queries": 1,
                     "busy_s_by_chip": {"/device:TPU:0": 0.021}}}
    assert reader("join_ms")(ctx) is None
    assert reader("exchange_ms")(ctx) is None
    assert reader("join_device_ms")(ctx) is None
    assert reader("exchange_device_ms")(ctx) is None
    assert reader("groupby_device_ms")(ctx) == 20.0
    assert PH.family_ms(ctx, "exec:groupby-") == 47.0
    # the parent's program: no phase span, no trace at all
    for name in NEW:
        assert reader(name)({"planes": {"devices": {}, "spans": []},
                             "trace": {}}) is None
        assert reader(name)({"planes": {}, "trace": None}) is None


def test_device_readers_sum_the_busiest_chips_programs_over_the_slice():
    spans = [("bench:accelerate", 100 * MS, 200 * MS),
             ("bench:collect", 200 * MS, 1_000 * MS),
             ("bench:accelerate", 1_000 * MS, 1_100 * MS),
             ("bench:collect", 1_100 * MS, 2_100 * MS)]
    chip0 = [
        ("jit_join_match/%sort u32[4194304] sort", 50 * MS, 150 * MS),
        # the slice opens at 100: 50 of these 100 ms count
        ("jit_join_match/%fusion.3 s64[4194304] fusion kLoop",
         300 * MS, 500 * MS),
        ("jit_join_expand/%gather f64[1048576] gather", 500 * MS, 560 * MS),
        # an operation on the line beside the one that holds it: once
        ("jit_join_expand/%while.body s32[] while", 520 * MS, 550 * MS),
        ("jit_exchange_split/%sort s32[65536] sort", 600 * MS, 630 * MS),
        ("jit_exchange_cut/%fusion f64[65536] fusion kLoop",
         630 * MS, 640 * MS),
        ("jit_agg_update/%fusion.7 f64[4096] fusion kCustom",
         700 * MS, 780 * MS),
        ("jit_agg_merge/%sort u32[8192] sort", 1_200 * MS, 1_220 * MS),
        ("jit_agg_eval/%fusion f64[4096] fusion kLoop",
         1_220 * MS, 1_224 * MS),
        ("jit__take/%gather f64[4096] gather", 1_300 * MS, 1_310 * MS),
        # past the slice's end: 30 of these 60 ms count
        ("jit_join_dense/%fusion s32[65536] fusion kLoop",
         2_070 * MS, 2_130 * MS),
    ]
    chip1 = [("jit_join_match/%sort u32[4194304] sort", 300 * MS, 400 * MS),
             ("jit_exchange_split/%sort s32[65536] sort",
              400 * MS, 420 * MS)]
    ctx = {"planes": {"devices": {"/device:TPU:0": chip0,
                                  "/device:TPU:1": chip1}, "spans": spans},
           "trace": {"queries": 2, "busy_s_by_chip": {
               "/device:TPU:0": 0.494, "/device:TPU:1": 0.120}}}
    join = (50 + 200 + 60 + 30) / 2
    exchange = (30 + 10) / 2
    groupby = (80 + 20 + 4) / 2
    assert reader("join_device_ms")(ctx) == pytest.approx(join)
    assert reader("exchange_device_ms")(ctx) == pytest.approx(exchange)
    assert reader("groupby_device_ms")(ctx) == pytest.approx(groupby)
    # what the three own is no more than the busiest chip's busy time
    assert 2 * (join + exchange + groupby) <= 494
    # another busiest chip, another reading
    ctx["trace"]["busy_s_by_chip"]["/device:TPU:1"] = 0.9
    assert reader("join_device_ms")(ctx) == pytest.approx(100 / 2)
    assert PG.device_ms_per_query(ctx, "jit_agg_") is None


def test_device_readers_on_a_trace_recorded_on_the_chip():
    """A q6 recorded on a v5e (PR 27): the aggregate's programs are its
    device time, and it ran no join and no exchange."""
    import os
    from benchmark.reduce import trace as TR
    recorded = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "q6_v5e_spans.xplane.pb.gz")
    planes = TR.read_planes(recorded)
    reduced = TR.reduce_planes(planes)
    queries = sum(n == "bench:collect" for n, _, _ in planes["spans"])
    ctx = {"planes": planes, "trace": dict(reduced, queries=queries)}
    (chip,) = planes["devices"]
    by_hand = sum(e - s for s, e in TR.union(
        [[s, e] for n, s, e in planes["devices"][chip]
         if n.startswith("jit_agg_")])) / 1e6 / queries
    got = reader("groupby_device_ms")(ctx)
    assert got == pytest.approx(by_hand) and got > 0
    assert got * queries / 1e3 <= reduced["busy_s_busiest"]
    assert reader("join_device_ms")(ctx) is None
    assert reader("exchange_device_ms")(ctx) is None
    assert reader("join_ms")(ctx) is None
    assert reader("exchange_ms")(ctx) is None
