#!/usr/bin/env python3
"""Reads, in one process on the cell's own chips and at its own size,
what a cell's limit is set from: over a list of seeds, the numbers the
program's answers give against the plain reference (the lower reading
is the largest), and those of the control of `benchmark/precision.py`
put in the program's place (the upper reading is the smallest).  On the
first `--fault-seeds` seeds it also reads each fault of
`benchmark/tests/faults.py` planted under the engine.  Run by hand; no
benchmark run calls it.

    python3 benchmark/tests/readings.py --workload <cell> \
        --seeds 101,102,103 [--runs 2] [--fault-seeds 3] [--rehearse]

It also says, seed by seed, how many XLA compile requests the seed's
first queries made: past the first seed that is new shapes from new
data (capacity buckets that depend on the data).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from benchmark import compare as CMP  # noqa: E402
from benchmark import manifest as MF  # noqa: E402
from benchmark import precision as PRC  # noqa: E402
from benchmark import run as RUN  # noqa: E402
import faults as FLT  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--runs", type=int, default=2)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = MF.Cell(MF.load(args.manifest), args.workload)
    devs = RUN.find_devices(cell, args.rehearse)
    from benchmark import engine as EN
    counter = EN.CompileCounter()
    refs = cell.references
    engines = {"program": EN.Engine(cell.config, False)}
    engines.update({f: FLT.broken_engine(f)(cell.config, False)
                    for f in FLT.FAULTS})
    generator = importlib.import_module(cell.config["generator"])
    scale = int(cell.config["rehearse_scale" if args.rehearse else "scale"])
    limit = float(cell.limits["float_rel_err"])
    rung = cell.limits["control"]

    def numbers_of(engine, tables, ref_all, runs):
        engine.register(tables)
        got, secs = [], []
        with engine.session():
            for _ in range(runs):
                for q in cell.queries:
                    a, clk = engine.run(q)
                    got.append(CMP.compare(a, ref_all[q], refs[q], limit))
                    secs.append(clk[2] - clk[0])
        engine.release()
        return CMP.worst(got), secs

    lower, upper = [], []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        tables = generator.generate(seed, scale, list(cell.reads()))
        ref_all = {q: refs[q].answer(tables) for q in cell.queries}
        c0 = counter.requests
        prog, secs = numbers_of(engines["program"], tables, ref_all,
                                args.runs)
        line = {"seed": seed, "program": prog, "query_s": secs,
                "compile_requests": counter.requests - c0}
        control = CMP.worst([CMP.compare(
            PRC.control_answer(refs[q], tables, rung), ref_all[q], refs[q],
            limit) for q in cell.queries])
        line["control"] = {rung: control["float_rel_err"],
                           "counts": sum(control[k] for k in CMP.COUNTS)}
        lower.append(prog["float_rel_err"])
        upper.append(control["float_rel_err"])
        if i < args.fault_seeds:
            line["faults"] = {}
            for f in FLT.FAULTS:
                n, _ = numbers_of(engines[f], tables, ref_all, 1)
                line["faults"][f] = {
                    "correct": CMP.verdict(n, limit)[0],
                    "numbers": {k: v for k, v in n.items() if v}}
        print(json.dumps({"reading": line}), flush=True)
    print(json.dumps({"readings_of": cell.name, "platform":
                      devs[0].platform, "kind": devs[0].device_kind,
                      "scale": scale, "seeds": len(lower),
                      "limit": limit, "control": rung,
                      "lower_max_program": max(lower),
                      "program_all": lower,
                      "upper_min_control": min(upper),
                      "control_all": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
