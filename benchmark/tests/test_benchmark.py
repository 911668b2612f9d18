"""Tests of the benchmark itself, on the CPU at `rehearse_scale`."""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

from benchmark import compare as CMP
from benchmark import load as LD
from benchmark import manifest as MF
from benchmark import precision as PRC
from benchmark import run as RUN
from benchmark.gen import tpch

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import faults as FLT  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = MF.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]

#: cells that are not in `BENCHMARK.json` (PERF.md, Open questions: not
#: yet proved on the chip), added here as new files and entries alone,
#: which is how a later PR brings them: a join, a group-by over string
#: keys, the join over a four-chip mesh, two queries in one mix from two
#: clients, and an open loop
CANDIDATES = {
    "cand-q3-join": {"traffic": "q3-closed-1"},
    "cand-q1-groupby": {"traffic": "q1-closed-1"},
    "cand-q3-mesh": {"traffic": "q3-closed-1", "chips": 4, "config": {
        "partitions": 4, "mesh_chips": 4, "conf": {
            "spark.rapids.shuffle.meshExchange.enabled": True}}},
    "cand-mix-2": {"traffic": "cand-q6-q1-closed-2", "mix": {
        "queries": [{"query": 6, "weight": 3}, {"query": 1, "weight": 1}],
        "loop": "closed", "clients": 2, "trace_queries": 4}},
    "cand-q6-open": {"traffic": "cand-q6-open", "mix": {
        "queries": [{"query": 6, "weight": 1}], "loop": "open",
        "clients": 2, "rate_per_s": 20.0, "burst": 2,
        "trace_queries": 4}},
}
ALL = CELLS + list(CANDIDATES)


@pytest.fixture(scope="session")
def candidates(tmp_path_factory):
    """Writes the candidates' files into the benchmark's directories (no
    file that is there is touched), and a manifest that lists them
    beside the real cells; takes the files away again."""
    tmp = tmp_path_factory.mktemp("candidates")
    made = []

    def write(path, text):
        assert not os.path.exists(path), path
        with open(path, "w") as f:
            f.write(text)
        made.append(path)

    m = json.loads(json.dumps(MANIFEST))
    base = MF.Cell(MANIFEST, CELLS[0]).config
    try:
        for name, c in CANDIDATES.items():
            extra = c.get("config", {})
            cfg = dict(base, **{k: v for k, v in extra.items()
                                if k != "conf"})
            cfg["conf"] = dict(base["conf"], **extra.get("conf", {}))
            cfile = tmp / f"{name}.json"
            cfile.write_text(json.dumps(cfg))
            if "mix" in c:
                write(os.path.join(MF.BENCH, "traffic",
                                   c["traffic"] + ".json"),
                      json.dumps(c["mix"]))
            write(os.path.join(MF.BENCH, "limits", name + ".json"),
                  json.dumps({"float_rel_err": 1e-10,
                              "control": "float32"}))
            m["configs"].append({"name": name + "-cfg",
                                 "source": cfg["source"] + " (candidate)",
                                 "file": str(cfile), "reduced": ["scale"],
                                 "why": "test"})
            m["workloads"].append({"name": name, "config": name + "-cfg",
                                   "traffic": c["traffic"],
                                   "chips": c.get("chips", 1),
                                   "why": "test"})
        write(os.path.join(MF.BENCH, "layer_metrics", "cand_spans.py"),
              "def read(ctx):\n"
              "    return len(ctx['planes'].get('spans', [])) + "
              "len(ctx['records'])\n")
        m["per_layer"].append({
            "name": "cand_spans", "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "entry + planner",
            "moves": "query_ms_p50", "workloads": list(CANDIDATES)})
        m["per_layer"].append({
            "name": "exchange_shard_chips", "unit": "count",
            "better": "higher", "source": "program_counter",
            "layer": "exchange", "moves": "query_ms_p50",
            "workloads": ["cand-q3-mesh"]})
        for x in m["per_layer"]:
            if x["name"] not in ("cand_spans", "exchange_shard_chips"):
                x["workloads"] = x["workloads"] + list(CANDIDATES)
        path = tmp / "BENCHMARK.json"
        path.write_text(json.dumps(m))
        yield str(path)
    finally:
        for path in made:
            os.remove(path)


def args_for(cell, manifest=None, **kw):
    base = dict(workload=cell, seed=7, seconds=0.5, trace=0, rehearse=True,
                control=None,
                manifest=manifest if cell in CANDIDATES else None)
    base.update(kw)
    return argparse.Namespace(**base)


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


# ---- the manifest keeps to the contract's shapes ------------------------
def test_manifest_names_and_units():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    assert m["paths"] == ["benchmark"]
    assert all(one_line(w) for w in m["command"])
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= {"bound"} if x in m["end_to_end"] else {"layer", "moves"}
        assert set(x) <= allowed, x
        for w in x.get("workloads", []):
            assert w in CELLS
    for x in m["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.25
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert x["moves"] in e2e and one_line(x["layer"])
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names))
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and c["file"].startswith("benchmark/")
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 2)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) \
        == len(m["workloads"])
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = MF.Cell(MANIFEST, name)
    entry = {c["name"]: c for c in MANIFEST["configs"]}[cell.entry["config"]]
    assert cell.config["source"] == entry["source"]
    assert cell.config["reduced"] == entry["reduced"]
    assert cell.config.get("mesh_chips", cell.chips) == cell.chips
    # a configuration holds what the harness reads and what it states
    assert set(cell.config) <= {
        "source", "suite", "generator", "queries", "sources", "scale",
        "rehearse_scale", "partitions", "mesh_chips", "conf",
        "guarantees", "reduced", "assumed"}
    assert cell.queries and cell.reads()
    for ref in cell.references.values():
        assert callable(ref.answer) and ref.READS
    assert cell.limits["float_rel_err"] > 0
    assert cell.limits["control"] in PRC.RUNGS
    for kind, folder in (("end_to_end", "end_to_end"),
                         ("per_layer", "layer_metrics")):
        for m in cell.metrics(kind):
            assert callable(MF.module_at(folder, m["name"] + ".py").read)
    # every cell reports setup_s, another end-to-end and a per-layer metric
    assert len(cell.metrics("end_to_end")) >= 2
    assert cell.metrics("per_layer")
    for m in cell.metrics("per_layer"):
        moved = {x["name"] for x in cell.metrics("end_to_end")}
        assert m["moves"] in moved


def test_every_file_name_is_of_allowed_characters():
    for folder, _, files in os.walk(MF.BENCH):
        if "__pycache__" in folder:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (folder, f)


def test_reference_imports_nothing_of_the_program():
    for folder in ("reference", "gen", "reduce"):
        for f in os.listdir(os.path.join(MF.BENCH, folder)):
            if f.endswith(".py"):
                text = open(os.path.join(MF.BENCH, folder, f)).read()
                assert "import spark_rapids_tpu" not in text
                assert "from spark_rapids_tpu" not in text
    for f in ("compare.py", "precision.py", "manifest.py", "load.py"):
        assert "spark_rapids_tpu" not in open(
            os.path.join(MF.BENCH, f)).read().replace(
                "spark_rapids_tpu/", "")


# ---- the generator -------------------------------------------------------
def test_generator_is_a_function_of_the_seed():
    big = 2 ** 31 + 12345
    a = tpch.generate(big, 5000)
    b = tpch.generate(big, 5000)
    c = tpch.generate(big + 1, 5000)
    for name in tpch.TABLES:
        assert a[name].equals(b[name]), name
    assert not a["lineitem"].equals(c["lineitem"])
    # a table does not depend on which others were asked for
    only = tpch.generate(big, 5000, ["lineitem"])
    assert only["lineitem"].equals(a["lineitem"])
    li, o = a["lineitem"], a["orders"]
    assert li.l_orderkey.isin(o.o_orderkey).all()
    assert (li.l_shipdate.to_numpy()
            > o.o_orderdate.to_numpy()[li.l_orderkey.to_numpy()]).all()
    assert list(li.columns) == [
        "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
        "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
        "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment"]


def test_generator_keeps_to_dbgen():
    """dbgen's row counts at SF1 and the specification's rules (clause
    4.2.3) that the three references read."""
    assert tpch.row_counts(6_000_000) == {
        "region": 5, "nation": 25, "supplier": 10_000,
        "customer": 150_000, "part": 200_000, "partsupp": 800_000,
        "orders": 1_500_000, "lineitem": 6_000_000}
    for scale in (20, 79, 5001, 60_000):
        t = tpch.generate(11, scale)
        li, o, p = t["lineitem"], t["orders"], t["part"]
        assert len(li) == scale
        per_order = li.groupby("l_orderkey").l_linenumber
        assert per_order.size().between(1, 7).all()
        assert (per_order.max() == per_order.size()).all()
        assert li.l_orderkey.is_monotonic_increasing
        assert ((o.o_custkey + 1) % 3 != 0).all()
    assert set(np.round(li.l_discount * 100)) == set(range(11))
    assert set(np.round(li.l_tax * 100)) == set(range(9))
    assert set(li.l_quantity) == set(map(float, range(1, 51)))
    price = p.p_retailprice.to_numpy()[li.l_partkey.to_numpy()]
    assert np.allclose(li.l_extendedprice, li.l_quantity * price, atol=0.006)
    assert 900.0 <= p.p_retailprice.min() and p.p_retailprice.max() < 2100
    odate = o.o_orderdate.to_numpy()[li.l_orderkey.to_numpy()]
    assert ((li.l_shipdate - odate).between(1, 121)).all()
    assert ((li.l_commitdate - odate).between(30, 90)).all()
    assert ((li.l_receiptdate - li.l_shipdate).between(1, 30)).all()
    assert (li.l_linestatus == "O").equals(li.l_shipdate > tpch.CURRENT_DATE)
    assert (li.l_returnflag == "N").equals(
        li.l_receiptdate > tpch.CURRENT_DATE)
    assert set(zip(li.l_returnflag, li.l_linestatus)) == {
        ("A", "F"), ("R", "F"), ("N", "F"), ("N", "O")}


def test_generator_matches_the_packages_schemas():
    from spark_rapids_tpu.models.tpch_data import SCHEMAS
    t = tpch.generate(3, 2000)
    for name, df in t.items():
        assert [f.name for f in SCHEMAS[name].fields] == list(df.columns)


# ---- the comparison ------------------------------------------------------
def _q3_like():
    ref = MF.module_at("reference", "tpch_q3.py")
    full = pd.DataFrame({
        "l_orderkey": np.arange(20, dtype=np.int64),
        "o_orderdate": np.arange(20, dtype=np.int32) + 9000,
        "o_shippriority": np.zeros(20, np.int32),
        "revenue": 1000.0 - 10.0 * np.arange(20)})
    return ref, full


def test_compare_passes_the_answer_and_a_tie_at_the_cut():
    ref, full = _q3_like()
    n = CMP.compare(full.head(10), full, ref, 1e-6)
    assert CMP.verdict(n, 1e-6)[0]
    tied = full.copy()
    tied.loc[10, "revenue"] = tied.loc[9, "revenue"] * (1 - 1e-9)
    got = tied.head(11).drop(index=9)          # the tie took the last seat
    assert CMP.verdict(CMP.compare(got, tied, ref, 1e-6), 1e-6)[0]


@pytest.mark.parametrize("fault,number", [
    ("float", "float_rel_err"), ("key", "keys_unmatched"),
    ("order", "order_breaks"), ("dropped", "rows_gap"),
    ("displaced", "topn_missed"), ("exact", "exact_cells_wrong")])
def test_compare_fails_each_kind_of_wrong_answer(fault, number):
    ref, full = _q3_like()
    got = full.head(10).copy()
    if fault == "float":
        got.loc[3, "revenue"] *= 1 + 1e-4
    elif fault == "key":
        got.loc[3, "l_orderkey"] = 999
    elif fault == "order":
        got = got.iloc[[1, 0] + list(range(2, 10))]
    elif fault == "dropped":
        got = got.head(9)
    elif fault == "displaced":
        got = full.iloc[list(range(9)) + [12]]
    elif fault == "exact":
        ref = MF.module_at("reference", "tpch_q1.py")
        full = pd.DataFrame({"l_returnflag": ["A", "N"],
                             "l_linestatus": ["F", "O"],
                             "sum_qty": [1.0, 2.0],
                             "count_order": [5, 6]})
        got = full.copy()
        got.loc[1, "count_order"] = 7
    n = CMP.compare(got, full, ref, 1e-6)
    ok, table = CMP.verdict(n, 1e-6)
    assert not ok and table[number][0] > table[number][1]


def test_the_control_is_the_reference_in_float32():
    ref = MF.module_at("reference", "tpch_q6.py")
    t = tpch.generate(5, 20_000, ["lineitem"])
    low = PRC.lower(t, "float32")["lineitem"]
    assert low.l_extendedprice.dtype == np.float32
    assert low.l_shipdate.dtype == t["lineitem"].l_shipdate.dtype
    got = PRC.control_answer(ref, t, "float32")
    n = CMP.compare(got, ref.answer(t), ref, 1e-10)
    assert 1e-10 < n["float_rel_err"] < 1e-5


# ---- the generator of load ----------------------------------------------
def test_every_seed_does_the_same_work_in_another_order():
    mix = {"queries": [{"query": 6, "weight": 3}, {"query": 1, "weight": 1}]}
    a, b = LD.sequence(mix, 1), LD.sequence(mix, 2 ** 31 + 9)
    rounds_a = [[next(a) for _ in range(4)] for _ in range(50)]
    rounds_b = [[next(b) for _ in range(4)] for _ in range(50)]
    assert all(sorted(r) == [1, 6, 6, 6] for r in rounds_a + rounds_b)
    assert rounds_a != rounds_b
    again = LD.sequence(mix, 1)
    assert rounds_a[0] == [next(again) for _ in range(4)]


class _Stub:
    """An engine that answers at once, for the generator of load."""

    def host_syncs(self):
        return 0

    def run(self, query, annotate=None):
        import time
        t = time.perf_counter()
        time.sleep(0.002)
        return query, (t, t, time.perf_counter())


class _NoTrace:
    annotate = None

    def after_query(self, query):
        pass


def test_open_loop_keeps_its_schedule_and_counts_the_wait():
    mix = {"queries": [{"query": 6}], "loop": "open", "clients": 1,
           "rate_per_s": 100.0, "burst": 4}
    w = LD.drive(_Stub(), mix, 3, 0.2, _NoTrace())
    rec = w["records"]
    assert len(rec) == 20 and w["errors"] == 0     # 100 a second for 0.2 s
    due = sorted(r["asked"] - w["opened"] for r in rec)
    assert due[:5] == pytest.approx([0, 0, 0, 0, 0.04], abs=1e-9)
    # the burst's later members waited for the earlier: their time counts
    assert rec[3]["end"] - rec[3]["asked"] > 3 * 0.002
    closed = LD.drive(_Stub(), dict(mix, loop="closed", clients=3), 3, 0.1,
                      _NoTrace())
    assert len(closed["records"]) > 60 and all(
        r["asked"] == r["start"] for r in closed["records"])


# ---- a whole run, rehearsed on the CPU ----------------------------------
@pytest.mark.parametrize("name", ALL)
def test_reference_equals_the_engine_at_rehearse_scale(name, candidates):
    result = RUN.run_cell(args_for(name, candidates))
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["rehearsed_on"] == "cpu"
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "compared"
    # a CPU run gives counts only: no time under a metric's name
    assert result["metrics"] == {}


@pytest.mark.parametrize("name", ALL)
def test_traced_rehearsal_reports_counts_only(name, candidates):
    result = RUN.run_cell(args_for(name, candidates, trace=1))
    assert result["correct"] is True
    cell = MF.Cell(MF.load(candidates), name)
    counts = {m["name"] for m in cell.metrics("per_layer")
              if m["source"] == "program_counter"}
    assert set(result["metrics"]) <= counts
    assert "compile_requests" in result["metrics"]
    assert "host_syncs" in result["metrics"]
    assert not os.path.exists(os.path.join(RUN.TRACE_DIR, name))
    if name in CANDIDATES:
        # a reader added as a new file reads the raw spans and records
        assert result["metrics"]["cand_spans"]["value"] >= 1
    if name == "cand-q3-mesh":
        assert result["metrics"]["exchange_shard_chips"]["value"] == 4
    if name == "cand-mix-2":
        assert result["attempted"] >= 4        # a round: q6 x 3 and q1


@pytest.mark.parametrize("name", ALL)
def test_control_comes_out_not_correct(name, candidates):
    rung = MF.Cell(MF.load(candidates), name).limits["control"]
    result = RUN.run_cell(args_for(name, candidates, control=rung))
    assert result["correct"] is False
    value, limit = (result["compared"]["float_rel_err"][k]
                    for k in ("value", "limit"))
    assert value > limit


# ---- the timed path broken underneath -----------------------------------
@pytest.mark.parametrize("name", [n for n in ALL if n != "cand-q6-open"])
@pytest.mark.parametrize("fault", FLT.FAULTS)
def test_broken_timed_path_reads_not_correct(name, fault, candidates):
    if fault == "key_altered" and "q6" in name:
        pytest.skip("q6's answer is one number: it has no key to alter")
    result = RUN.run_cell(args_for(name, candidates),
                          engine_factory=FLT.broken_engine(fault))
    assert result["correct"] is False, (fault, result["compared"])
    assert result["failed"] >= 1
    if name != "cand-mix-2" or fault != "key_altered":
        assert result["failed"] == result["attempted"]


def test_mesh_exchange_left_out_reads_not_correct(monkeypatch, candidates):
    """The exchange between chips left out: a chip receives nothing
    from the others and keeps only the block it would have sent itself,
    so the rows bound for other chips never arrive.  (An exchange that
    merely leaves every row where it was does NOT change this engine's
    answers: its hash join concatenates the whole build side and
    streams every probe partition, whatever the partitioning.)"""
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
        result = RUN.run_cell(args_for("cand-q3-mesh", candidates, seed=21))
    finally:
        monkeypatch.undo()
        clear_kernel_cache()
        jax.clear_caches()
    assert result["correct"] is False, result["compared"]


# ---- the command, as the driver calls it --------------------------------
def _run_command(*extra, env=None):
    cmd = [sys.executable] + MANIFEST["command"][1:] + [
        "--workload", "sf025-q6-scan", "--seed", str(2 ** 31 + 3),
        "--seconds", "0.5", "--trace", "0", *extra]
    return subprocess.run(cmd, cwd=MF.ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def test_rehearse_prints_the_contracts_last_line():
    env = dict(os.environ, BENCH_RUN="ignored")
    p = _run_command("--rehearse", env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert last["correct"] is True and list(last)[-1] == "compared"
    tail = p.stderr.strip().splitlines()[-8:]
    assert tail[-1] == "correct: True"
    assert any(x.startswith("compared float_rel_err:") for x in tail)


def test_without_a_tpu_nothing_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run_command(env=env)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_without_the_program_nothing_runs(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark."""
    shutil.copy(os.path.join(MF.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(MF.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "sf025-q6-scan",
         "--seed", "1", "--seconds", "0.5", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
