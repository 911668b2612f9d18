#!/usr/bin/env python3
"""Records the small trace that `test_reduce.py` reduces to known
numbers: one traced run of a cell at a scale given here, through
`run_cell`, with the trace file copied out before it is deleted, then
prints what `reduce_planes` reads from it.  Run by hand on the chip;
`benchmark/tests/data/q6_v5e_small.xplane.pb.gz` came from
`--workload sf025-q6-scan --scale 20000 --seconds 0.1`.

    python3 benchmark/tests/record_trace.py --workload <cell> \
        --scale 20000 --seconds 0.1 --out <file.xplane.pb.gz>
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import manifest as MF  # noqa: E402
from benchmark import run as RUN  # noqa: E402
from benchmark.reduce import trace as TR  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    # the cell at another scale is a manifest and a configuration of
    # its own, written beside the trace: the harness is driven by data
    manifest = MF.load()
    cell = MF.Cell(manifest, args.workload)
    scratch = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(args.out)))
    config = dict(cell.config, scale=args.scale, rehearse_scale=args.scale)
    cfile = os.path.join(scratch, "config.json")
    with open(cfile, "w") as f:
        json.dump(config, f)
    for c in manifest["configs"]:
        if c["name"] == cell.entry["config"]:
            c["file"] = os.path.relpath(cfile, MF.ROOT)
    mfile = os.path.join(scratch, "BENCHMARK.json")
    with open(mfile, "w") as f:
        json.dump(manifest, f)

    read_planes = TR.read_planes

    def keeping(path, *a, **kw):
        with open(path, "rb") as src, gzip.open(args.out, "wb") as dst:
            dst.write(src.read())
        return read_planes(path, *a, **kw)
    TR.read_planes = keeping
    result = RUN.run_cell(argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=1, rehearse=args.rehearse, control=None, manifest=mfile))
    TR.read_planes = read_planes
    shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps({"correct": result["correct"], "kept": args.out,
                      "reduced": TR.reduce_planes(read_planes(args.out))
                      if os.path.exists(args.out) else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
