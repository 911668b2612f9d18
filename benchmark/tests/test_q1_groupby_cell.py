"""The cell `sf1-q1-groupby` as `BENCHMARK.json` has it: found by name,
rehearsed on the CPU (correct; counts only; the float32 control not
correct), and its five readers on spans and device operations written
out by hand."""
import argparse

import pytest

from benchmark import manifest as MF
from benchmark import run as RUN
from benchmark.reduce import phases as PH
from benchmark.reduce import programs as PG

CELL = "sf1-q1-groupby"
CONFIG = "tpch-sf1-1chip-defaults"
MANIFEST = MF.load()
NEW = ("upload_strings_ms", "groupby_ms", "groupby_update_device_ms",
       "groupby_merge_device_ms", "groupby_concat_device_ms")
#: the lists the issue appends the cell to, and those it may not touch
SHARED = ("accelerate_ms", "gc_ms_per_query", "first_query_s",
          "compile_requests", "host_syncs", "hbm_roofline_pct",
          "device_idle_pct", "peak_hbm_gb")
PINNED = ("upload_ms", "upload_put_ms", "upload_gb_per_s", "plan_self_ms",
          "readback_ms", "join_ms", "exchange_ms", "join_device_ms",
          "exchange_device_ms", "groupby_device_ms")
MS = 1_000_000


def reader(name):
    return MF.module_at("layer_metrics", name + ".py").read


def args_for(**kw):
    base = dict(workload=CELL, seed=2 ** 31 + 33, seconds=0.5, trace=0,
                rehearse=True, control=None, manifest=None)
    base.update(kw)
    return argparse.Namespace(**base)


# ---- the entries ---------------------------------------------------------
def test_the_cell_is_as_the_issue_names_it():
    """Nothing cut, and the plugin's default conf: the configuration is
    `tpch-sf1-1chip`'s data under `tpch-sf025-1chip-defaults`' conf and
    guarantees, word for word, with q1's clause as its source."""
    cell = MF.Cell(MANIFEST, CELL)
    assert cell.entry["config"] == CONFIG
    assert cell.entry["traffic"] == "q1-closed-1" and cell.chips == 1
    assert cell.queries == [1]
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 1
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == [] == cell.config["reduced"]
    assert entry["source"] == cell.config["source"]
    assert "clause 2.4.1" in entry["source"] and len(entry["source"]) <= 200
    assert cell.config["scale"] == 6_000_000
    defaults = MF.Cell(MANIFEST, "sf025-q3-join").config
    assert {k for k in cell.config
            if cell.config[k] != defaults.get(k)} == {
        "source", "scale", "reduced"}
    assert cell.config["conf"] == defaults["conf"]
    assert cell.config["guarantees"] == defaults["guarantees"]
    sf1 = MF.Cell(MANIFEST, "sf1-q6-scan").config
    assert {k for k in cell.config if cell.config[k] != sf1.get(k)} == {
        "source", "conf", "guarantees"}
    # no lane switch and no deadline of its own: what the program's
    # defaults are is what a cold run of the cell has to pass under
    assert not [k for k in cell.config["conf"]
                if "Groupby" in k or "watchdog" in k]
    assert cell.config["conf"]["spark.rapids.sql.test.enabled"] is True
    assert cell.limits["float_rel_err"] == 1e-10
    assert cell.limits["control"] == "float32"


def test_the_cell_reports_what_the_issue_lists():
    cell = MF.Cell(MANIFEST, CELL)
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {"setup_s", "query_ms_p50", "input_rows_per_s"}
    layers = {m["name"] for m in cell.metrics("per_layer")}
    assert layers == set(NEW) | set(SHARED)
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "query_ms_p50"
        assert by_name[name]["unit"] == "ms"
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL
    for name in PINNED:
        assert CELL not in by_name[name]["workloads"]
    assert {n: (by_name[n]["source"], by_name[n]["layer"]) for n in NEW} == {
        "upload_strings_ms": ("program_span", "scan + upload"),
        "groupby_ms": ("program_span", "operators"),
        "groupby_update_device_ms": ("device_trace", "kernels"),
        "groupby_merge_device_ms": ("device_trace", "kernels"),
        "groupby_concat_device_ms": ("device_trace", "kernels")}


def test_the_limit_lies_a_factor_of_ten_from_both_readings():
    limits = MF.Cell(MANIFEST, CELL).limits
    lower = limits["set_from"]["lower"]["reading"]
    upper = limits["set_from"]["upper"]["reading"]
    assert 0 < lower * 10 <= limits["float_rel_err"] <= upper / 10
    assert "SF1 " in limits["set_from"]["lower"]["of"]
    assert "12 seeds" in limits["set_from"]["lower"]["of"]


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
    # the programs may all be in this process already: 0 is a count
    assert result["metrics"]["compile_requests"]["value"] >= 0


def test_the_float32_control_is_not_correct():
    result = RUN.run_cell(args_for(control="float32"))
    assert result["correct"] is False
    row = result["compared"]["float_rel_err"]
    assert row["value"] > row["limit"]
    assert all(result["compared"][k]["value"] == 0
               for k in result["compared"] if k != "float_rel_err")


@pytest.mark.parametrize("fault", ["answer_altered", "half_the_rows",
                                   "key_altered"])
def test_a_planted_fault_is_not_correct(fault):
    import faults as FLT
    result = RUN.run_cell(args_for(), engine_factory=FLT.broken_engine(fault))
    assert result["correct"] is False
    over = {k for k, row in result["compared"].items()
            if row["value"] > row["limit"]}
    assert over and over <= {
        "answer_altered": {"float_rel_err"},
        "half_the_rows": {"float_rel_err", "exact_cells_wrong"},
        "key_altered": {"keys_unmatched", "order_breaks",
                        "exact_cells_wrong", "float_rel_err"}}[fault]


# ---- the five readers, by hand -------------------------------------------
def q1_spans(t0, scale=1, strings=(60, 40)):
    """One query's spans, ms from `t0` (times `scale`): two partitions
    upload, each put holding its string columns' span; the final
    aggregate's update pulls the exchange, whose map side runs the
    partial aggregate's update and merge of both partitions."""
    def span(name, lo, hi):
        return (name, t0 + lo * scale * MS, t0 + hi * scale * MS)
    out = [
        span("bench:accelerate", 0, 200),
        span("exec:SourceUpload[s0]", 2, 198),
        span("exec:upload-convert", 2, 10),
        span("exec:upload-put", 10, 100),
        span("exec:upload-convert", 100, 108),
        span("exec:upload-put", 108, 198),
        span("bench:collect", 200, 400),
        span("exec:SortExec[p0]", 201, 399),
        span("exec:groupby-update", 204, 380),          # final, p0
        span("exec:exchange-write", 206, 370),
        span("exec:groupby-update", 208, 280),          # partial, p0
        span("exec:groupby-merge", 280, 290),
        span("exec:groupby-update", 292, 352),          # partial, p1
        span("exec:groupby-merge", 352, 366),
        span("exec:exchange-read", 372, 378),
        span("exec:groupby-merge", 380, 386),           # final, p0
        span("exec:Readback", 390, 398),
    ]
    if strings:
        out += [span("exec:upload-strings", 11, 11 + strings[0]),
                span("exec:upload-strings", 109, 109 + strings[1])]
    return out


def owned_by_hand():
    """Every instant to the phase span opened last:
    group-by  [204,206) [208,290) [292,366) [380,386)  = 2+82+74+6 = 164
    exchange  [206,208) [290,292) [366,370) [372,378)  = 2+2+4+6 = 14
    and [370,372), [378,380) fall back to the final update = +4 -> 168"""
    return {"exec:groupby-": 168, "exec:exchange-": 14}


def test_phase_ownership_by_hand():
    spans = q1_spans(1_000 * MS)
    (lo, hi), = [(s, e) for n, s, e in spans if n == "bench:collect"]
    got = PH.owned_ns(spans, lo, hi)
    assert {k: v / MS for k, v in got.items()} == owned_by_hand()
    assert sum(got.values()) <= hi - lo


def test_host_readers_take_the_median_query():
    a = q1_spans(1_000 * MS)
    b = q1_spans(2_000 * MS, scale=2)
    c = q1_spans(4_000 * MS, scale=3)
    outside = [("exec:upload-strings", 500 * MS, 600 * MS),  # warm-up's
               ("exec:groupby-merge", 3_900 * MS, 3_950 * MS)]
    ctx = {"planes": {"devices": {}, "spans": a + b + c + outside}}
    assert reader("upload_strings_ms")(ctx) == 2 * (60 + 40)
    assert reader("groupby_ms")(ctx) == 2 * owned_by_hand()["exec:groupby-"]
    # the strings are inside the put, and the put inside the upload
    put = MF.module_at("layer_metrics", "upload_put_ms.py").read(ctx)
    assert reader("upload_strings_ms")(ctx) < put == 2 * 180


def test_a_query_without_the_span_is_left_out_and_none_reads_nothing():
    with_span = q1_spans(1_000 * MS, strings=(50, 30))
    without = q1_spans(2_000 * MS, strings=None)
    ctx = {"planes": {"devices": {}, "spans": with_span + without}}
    assert reader("upload_strings_ms")(ctx) == 80
    # the parent's program: puts and group-by spans, no string span
    ctx = {"planes": {"devices": {}, "spans": without}, "trace": {}}
    assert reader("upload_strings_ms")(ctx) is None
    assert reader("groupby_ms")(ctx) == 168
    assert reader("groupby_update_device_ms")(ctx) is None
    # a q6: an ungrouped reduce opens no group-by span and runs no
    # grouped program
    q6 = [("bench:accelerate", 0, 30 * MS),
          ("exec:upload-put", 2 * MS, 28 * MS),
          ("bench:collect", 30 * MS, 90 * MS),
          ("exec:HashAggregateExec[p0]", 31 * MS, 80 * MS)]
    devices = {"/device:TPU:0": [
        ("jit_agg_reduce_update/%fusion f64[] fusion kCustom",
         40 * MS, 60 * MS)]}
    ctx = {"planes": {"devices": devices, "spans": q6},
           "trace": {"queries": 1,
                     "busy_s_by_chip": {"/device:TPU:0": 0.020}}}
    for name in NEW:
        assert reader(name)(ctx) is None, name
        # no span, no trace at all
        assert reader(name)({"planes": {"devices": {}, "spans": []},
                             "trace": {}}) is None
        assert reader(name)({"planes": {}, "trace": None}) is None


def test_device_readers_split_the_aggregates_programs():
    spans = [("bench:accelerate", 100 * MS, 300 * MS),
             ("bench:collect", 300 * MS, 1_000 * MS),
             ("bench:accelerate", 1_000 * MS, 1_200 * MS),
             ("bench:collect", 1_200 * MS, 2_000 * MS)]
    chip0 = [
        # before the slice opens at 100: 20 of these 50 ms count
        ("jit_agg_update/%sort u32[65536] sort", 70 * MS, 120 * MS),
        ("jit_upload_split/%fusion.4 f64[65536] fusion", 150 * MS, 160 * MS),
        ("jit_agg_update/%fusion.7 f64[65536] fusion kCustom",
         310 * MS, 500 * MS),
        # a loop and its body on the line beside it: once
        ("jit_agg_update/%while s32[] while", 500 * MS, 560 * MS),
        ("jit_agg_update/%while.body f64[65536] fusion", 510 * MS, 550 * MS),
        ("jit_agg_concat/%concatenate f64[1048576] concatenate",
         600 * MS, 610 * MS),
        ("jit_agg_merge/%sort u32[1048576] sort", 610 * MS, 700 * MS),
        ("jit_agg_eval/%fusion f64[32] fusion kLoop", 700 * MS, 702 * MS),
        ("jit_exchange_split/%sort s32[32] sort", 710 * MS, 712 * MS),
        ("jit_agg_update/%fusion.7 f64[65536] fusion kCustom",
         1_210 * MS, 1_400 * MS),
        # past the slice's end: 10 of these 40 ms count
        ("jit_agg_merge/%sort u32[1048576] sort", 1_990 * MS, 2_030 * MS),
        # the ungrouped lane is neither
        ("jit_agg_reduce_update/%fusion f64[] fusion", 1_500 * MS, 1_520 * MS),
    ]
    chip1 = [("jit_agg_update/%sort u32[65536] sort", 300 * MS, 330 * MS)]
    ctx = {"planes": {"devices": {"/device:TPU:0": chip0,
                                  "/device:TPU:1": chip1}, "spans": spans},
           "trace": {"queries": 2, "busy_s_by_chip": {
               "/device:TPU:0": 0.604, "/device:TPU:1": 0.030}}}
    update = (20 + 190 + 60 + 190) / 2
    merge = (90 + 10) / 2
    concat = 10 / 2
    assert reader("groupby_update_device_ms")(ctx) == pytest.approx(update)
    assert reader("groupby_merge_device_ms")(ctx) == pytest.approx(merge)
    assert reader("groupby_concat_device_ms")(ctx) == pytest.approx(concat)
    # the three are disjoint parts of what `groupby_device_ms` reads,
    # and eval and the reduce lane are the rest of it
    whole = PG.device_ms_per_query(ctx, "jit_agg_")
    assert whole == pytest.approx(update + merge + concat + (2 + 20) / 2)
    assert 2 * whole <= 604
    # another busiest chip, another reading
    ctx["trace"]["busy_s_by_chip"]["/device:TPU:1"] = 0.9
    assert reader("groupby_update_device_ms")(ctx) == pytest.approx(30 / 2)
    assert reader("groupby_merge_device_ms")(ctx) is None
    assert reader("groupby_concat_device_ms")(ctx) is None
