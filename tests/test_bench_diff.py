"""bench_diff unit tests (scripts/bench_diff.py): synthetic-round
regression detection, direction awareness (rows/s up = good, wall_ms
down = good), missing/errored-phase tolerance, both round formats
(driver wrapper with tail + submetrics fallback, raw JSON lines),
attribution notes, and round files staying parseable."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "scripts"))
import bench_diff as BD  # noqa: E402

REPO = os.path.join(os.path.dirname(__file__), "..")


def _round(*recs):
    return BD.parse_round("\n".join(json.dumps(r) for r in recs))


BASE = [
    {"metric": "tpch_q1_rows_per_sec", "value": 100.0,
     "vs_baseline": 2.0,
     "util": {"samples": 50, "busy": 60.0, "idle": 40.0}},
    {"metric": "groupby_sf1_wall_ms", "value": 50.0},
    {"metric": "udf_q27_rows_per_sec", "value": 10.0},
]


def test_regression_detected_higher_better():
    a = _round(*BASE)
    b = _round({**BASE[0], "value": 70.0,
                "util": {"samples": 50, "busy": 20.0, "idle": 80.0}},
               BASE[1], BASE[2])
    rep = BD.compare_rounds(a, b, threshold=10.0)
    assert rep["regressions"] == ["tpch_q1_rows_per_sec"]
    lane = next(l for l in rep["lanes"]
                if l["metric"] == "tpch_q1_rows_per_sec")
    assert lane["status"] == "regressed"
    assert any(n.startswith("util.") for n in lane["attribution"])


def test_regression_detected_lower_better():
    a = _round(*BASE)
    b = _round(BASE[0], {**BASE[1], "value": 90.0}, BASE[2])
    rep = BD.compare_rounds(a, b, threshold=10.0)
    assert rep["regressions"] == ["groupby_sf1_wall_ms"]


def test_improvement_passes_both_directions():
    a = _round(*BASE)
    b = _round({**BASE[0], "value": 150.0},
               {**BASE[1], "value": 30.0},
               {**BASE[2], "value": 10.2})
    rep = BD.compare_rounds(a, b, threshold=10.0)
    assert rep["regressions"] == []
    statuses = {l["metric"]: l["status"] for l in rep["lanes"]}
    assert statuses["tpch_q1_rows_per_sec"] == "improved"
    assert statuses["groupby_sf1_wall_ms"] == "improved"
    assert statuses["udf_q27_rows_per_sec"] == "flat"


def test_missing_phase_tolerated():
    a = _round(*BASE)
    b = _round(BASE[0],
               {"metric": "udf_q27_rows_per_sec", "value": 0,
                "error": "TimeoutError: wall cap"},
               {"metric": "brand_new_lane_rows_per_sec", "value": 5.0})
    rep = BD.compare_rounds(a, b, threshold=10.0)
    assert rep["regressions"] == []
    assert "groupby_sf1_wall_ms" in rep["removed"]
    assert "brand_new_lane_rows_per_sec" in rep["added"]
    inc = [l for l in rep["lanes"] if l["status"] == "incomparable"]
    assert len(inc) == 1 and inc[0]["metric"] == "udf_q27_rows_per_sec"


def test_kernel_and_edge_attribution():
    a = _round({"metric": "groupby_sf1_sort_rows_per_sec",
                "value": 100.0,
                "kernels": [{"label": "sort", "device_ms": 100.0},
                            {"label": "agg-update",
                             "device_ms": 20.0}]})
    b = _round({"metric": "groupby_sf1_sort_rows_per_sec",
                "value": 60.0,
                "kernels": [{"label": "sort", "device_ms": 400.0},
                            {"label": "agg-update",
                             "device_ms": 21.0}]})
    rep = BD.compare_rounds(a, b, threshold=10.0)
    lane = rep["lanes"][0]
    assert lane["status"] == "regressed"
    assert any("kernel[sort]" in n for n in lane["attribution"]), lane


def test_wrapper_and_submetrics_formats():
    tail = "\n".join(json.dumps(r) for r in BASE)
    wrapped = BD.parse_round(json.dumps({"n": 7, "rc": 0,
                                         "tail": tail}))
    assert set(wrapped["metrics"]) == {m["metric"] for m in BASE}
    # a truncated round recovers lanes from the summary's submetrics
    summary = {"metric": "tpch_q1_rows_per_sec", "value": 100.0,
               "hbm_probe_gbps": 3.0, "host_syncs": 10,
               "submetrics": [
                   {"metric": "tpch_q1_rows_per_sec", "value": 100.0},
                   {"metric": "join_sort_q3_rows_per_sec",
                    "value": 7.0}]}
    trunc = BD.parse_round(json.dumps({"n": 5, "rc": 124,
                                       "tail": json.dumps(summary)}))
    assert trunc["summary"] is not None
    assert "join_sort_q3_rows_per_sec" in trunc["metrics"]


@pytest.mark.parametrize("rounds", [("round_a.json", "round_b.json")])
def test_committed_rounds_parse_and_diff(rounds, tmp_path):
    """Two round FILES in the driver's wrapper shape load and diff (the
    repo commits no bench rounds of its own: the driver's ledger is the
    record)."""
    for name, scale in zip(rounds, (1.0, 0.9)):
        tail = "\n".join(json.dumps({**m, "value": m["value"] * scale})
                         for m in BASE)
        (tmp_path / name).write_text(json.dumps(
            {"n": 1, "rc": 0, "tail": tail}))
    a = BD.load_round(str(tmp_path / rounds[0]))
    b = BD.load_round(str(tmp_path / rounds[1]))
    assert a["metrics"], "old round parsed no lanes"
    rep = BD.compare_rounds(a, b)
    # report renders without error regardless of lane overlap
    text = BD.format_report(rep, *rounds)
    assert "verdict:" in text


def test_cli_selftest_and_gate_exit_codes(tmp_path):
    script = os.path.join(REPO, "scripts", "bench_diff.py")
    r = subprocess.run([sys.executable, script, "--selftest"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    good = tmp_path / "a.json"
    bad = tmp_path / "b.json"
    good.write_text("\n".join(json.dumps(m) for m in BASE))
    bad.write_text(json.dumps(
        {"metric": "tpch_q1_rows_per_sec", "value": 50.0}))
    # injected synthetic regression -> non-zero exit (the CI gate)
    r = subprocess.run([sys.executable, script, str(good), str(bad)],
                       capture_output=True, text=True)
    assert r.returncode == 1, r.stdout
    assert "regressed" in r.stdout
    # --no-gate reports but never fails
    r = subprocess.run([sys.executable, script, str(good), str(bad),
                        "--no-gate"], capture_output=True, text=True)
    assert r.returncode == 0
    # improvement passes the gate
    better = tmp_path / "c.json"
    better.write_text("\n".join(json.dumps(
        {**m, "value": m["value"] * (0.5 if "wall" in m["metric"]
                                     else 2.0)}) for m in BASE))
    r = subprocess.run([sys.executable, script, str(good),
                        str(better)], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout
