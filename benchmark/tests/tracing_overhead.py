#!/usr/bin/env python3
"""What the program's own tracing costs a query: alternating windows of
one cell with and without `spark.rapids.sql.profile.enabled`, in one
process, the jax profiler off in both.  Run by hand on the chip:

    python3 benchmark/tests/tracing_overhead.py --workload sf025-q6-scan \
        --pairs 6 --seconds 51 --seed 2147489001

Set-up is `run.py`'s (first run, warm-up, collect and freeze), once for
both engines; each window is the traffic file's, from a seed of its
own; every other pair starts with the other side.  Prints one line a
window and a last line with both sides' medians and the paired
differences of `query_ms_p50`."""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest as MF  # noqa: E402
from benchmark import run as RUN  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pairs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    cell = MF.Cell(MF.load(), args.workload)
    devs = RUN.find_devices(cell, args.rehearse)
    from benchmark import engine as EN
    config, traffic = cell.config, cell.traffic
    driver = importlib.import_module(traffic.get("driver", "benchmark.load"))
    scale = int(config["rehearse_scale" if args.rehearse else "scale"])
    tables = importlib.import_module(config["generator"]).generate(
        args.seed, scale, list(cell.reads()))
    engines = {"off": EN.Engine(config, False), "on": EN.Engine(config, True)}
    idle = RUN.Tracer(cell, 0, 0.0)          # never started: no profiler
    for engine in engines.values():
        engine.register(tables)
        for _ in range(1 + int(traffic.get("warmup_max", 4))):
            for q in cell.queries:
                engine.run(q)
    gc.collect()
    gc.freeze()
    windows = {"off": [], "on": []}
    for pair in range(args.pairs):
        order = ("off", "on") if pair % 2 == 0 else ("on", "off")
        for side in order:
            got = driver.drive(engines[side], traffic,
                               args.seed + 1 + pair, args.seconds, idle)
            ms = [(r["end"] - r["asked"]) * 1e3 for r in got["records"]]
            p50 = statistics.median(ms)
            windows[side].append(p50)
            print(json.dumps({"pair": pair, "profile": side, "queries":
                              len(ms), "query_ms_p50": p50,
                              "errors": got["errors"]}), flush=True)
    diffs = [100.0 * (on - off) / off
             for on, off in zip(windows["on"], windows["off"])]
    print(json.dumps({
        "workload": cell.name, "device": devs[0].device_kind,
        "rehearsed": args.rehearse, "pairs": args.pairs,
        "seconds": args.seconds,
        "p50_off": statistics.median(windows["off"]),
        "p50_on": statistics.median(windows["on"]),
        "paired_overhead_pct": diffs,
        "overhead_pct_median": statistics.median(diffs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
