"""Finds everything a cell needs by the names in `BENCHMARK.json`.

A cell names its configuration and its traffic mix; the configuration
names its suite, generator and query module; the traffic lists its
queries and names the generator of load that reads it.  Configurations,
traffic mixes, references, limits and metric readers are files in
directories this module lists, so a later PR adds one as a new file and
a new entry and edits nothing that is there:

    configs/<configuration>.json      the deployment as it is run
    traffic/<traffic>.json            the mix: queries with weights,
                                      loop, clients, rate, bursts
    reference/<suite>_q<query>.py     the plain reference of one query
    limits/<cell>.json                the cell's float limit and where
                                      it was read from
    end_to_end/<metric>.py            `read(ctx)` of an end-to-end metric
    layer_metrics/<metric>.py         `read(ctx)` of a per-layer metric
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def load(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        return json.load(f)


def module_at(*parts):
    """The Python file at benchmark/<parts> as a module, found by path:
    a metric's name may hold `.` or `-`, which no import statement
    takes."""
    path = os.path.join(BENCH, *parts)
    if not os.path.exists(path):
        raise FileNotFoundError(f"benchmark: no file {path}")
    name = "benchmark_" + "_".join(parts).replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads` with every file it names, loaded."""

    def __init__(self, manifest: dict, name: str):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"benchmark: no workload {name!r} in "
                             f"BENCHMARK.json; there are {sorted(cells)}")
        self.manifest = manifest
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in manifest["configs"]}
        with open(os.path.join(ROOT, configs[self.entry["config"]]["file"]),
                  encoding="utf-8") as f:
            self.config = json.load(f)
        self.traffic = _json("traffic", self.entry["traffic"] + ".json")
        self.queries = sorted({int(q["query"])
                               for q in self.traffic["queries"]})
        self.references = {q: module_at(
            "reference", f"{self.config['suite']}_q{q}.py")
            for q in self.queries}
        self.limits = _json("limits", name + ".json")

    def reads(self) -> dict:
        """{table: [columns]} that the mix's queries read, together."""
        out = {}
        for ref in self.references.values():
            for table, cols in ref.READS.items():
                out.setdefault(table, [])
                out[table] += [c for c in cols if c not in out[table]]
        return out

    def metrics(self, kind: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports: an
        entry without `workloads` is every cell's."""
        return [m for m in self.manifest[kind]
                if "workloads" not in m or self.name in m["workloads"]]

    def read_metrics(self, kind: str, ctx: dict) -> dict:
        """{name: {"value", "unit"}} from each metric's own reader.  A
        reader that finds nothing to read returns None and the metric
        is left out of the line."""
        folder = {"end_to_end": "end_to_end",
                  "per_layer": "layer_metrics"}[kind]
        out = {}
        for m in self.metrics(kind):
            value = module_at(folder, m["name"] + ".py").read(ctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
