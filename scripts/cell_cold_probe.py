#!/usr/bin/env python3
"""What a benchmark cell's set-up costs from an emptied compile cache,
and what a new seed adds on a filled one.  Run by hand on the chip:

    chiprun --timeout 3000 -- python3 scripts/cell_cold_probe.py \
        --workload sf025-q3-join --tag parent --seeds 2900000001,2900000002,2900000003

Steps, each a process of its own (`benchmark/run.py`, as the driver
runs it; this parent never imports JAX, so the chip is the child's):

    cold       the cache directory emptied, the first seed
    off        the same seed under `--off-config` with the cell's traffic
               (a manifest written beside; the cache as `cold` left it)
    seed<k>    every further seed on the filled cache
    traced     `--trace 1` on the last seed, if `--traced`; the device
               time of every program (not the benchmark's ten operations)
               goes to `traced.by_program.json`

With `JAX_LOG_COMPILES` on, JAX logs every compile with its name and
seconds; the ten slowest of each step are listed.  Everything goes to
`chiprun_out/<tag>/`, and a summary line per step to standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FINISHED = re.compile(
    r"Finished XLA compilation of (\S+) in ([0-9.eE+\-]+) sec")
CACHE_HIT = re.compile(r"[Pp]ersistent compilation cache hit for '([^']+)'")


#: `benchmark/run.py` with the trace's reduction listened to: the
#: busiest chip's time by program over the traced slice, every program
BY_PROGRAM = """
import json, os, runpy, sys
sys.path.insert(0, os.getcwd())
sys.argv[0] = "benchmark/run.py"
from benchmark.reduce import trace as TR
real = TR.reduce_planes
def listen(planes, *a, **k):
    out = real(planes, *a, **k)
    marks = [(s, e) for n, s, e in planes["spans"] if n.startswith("bench:")]
    if out and marks:
        lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
        chip = max(out["busy_s_by_chip"], key=out["busy_s_by_chip"].get)
        by = {}
        for name, s, e in planes["devices"][chip]:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                prog = name.split("/", 1)[0]
                by[prog] = by.get(prog, 0.0) + d / 1e9
        json.dump({"busy_s": out["busy_s_busiest"], "window_s":
                   out["window_s"], "by_program_s": dict(sorted(
                       by.items(), key=lambda kv: -kv[1]))},
                  open(os.environ["BY_PROGRAM_OUT"], "w"), indent=1)
    return out
TR.reduce_planes = listen
runpy.run_path("benchmark/run.py", run_name="__main__")
"""


def run_step(name, argv, out_dir, env):
    t0 = time.perf_counter()
    env = dict(env, BY_PROGRAM_OUT=os.path.join(
        out_dir, name + ".by_program.json"))
    with open(os.path.join(out_dir, name + ".out"), "w") as so, \
            open(os.path.join(out_dir, name + ".err"), "w") as se:
        rc = subprocess.run([sys.executable, "-c", BY_PROGRAM] + argv,
                            cwd=ROOT, env=env, stdout=so, stderr=se).returncode
    wall = time.perf_counter() - t0
    lines = {}
    for line in open(os.path.join(out_dir, name + ".out")):
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        lines.update(obj if "correct" not in obj else {"result": obj})
    err = open(os.path.join(out_dir, name + ".err")).read()
    compiled = sorted(((float(s), n) for n, s in FINISHED.findall(err)),
                      reverse=True)
    setup = lines.get("setup", {})
    result = lines.get("result", {})
    summary = {
        "step": name, "rc": rc, "wall_s": round(wall, 2),
        "setup_s": setup.get("setup_s"), "first": setup.get("first"),
        "warm_up": setup.get("warm_up"),
        "compiled": len(compiled),
        "compile_s": round(sum(s for s, _ in compiled), 2),
        "cache_hits_logged": len(CACHE_HIT.findall(err)),
        "slowest": [[round(s, 2), n] for s, n in compiled[:10]],
        "correct": result.get("correct"),
        "metrics": {k: v["value"] for k, v in
                    result.get("metrics", {}).items()},
        "compared": {k: v["value"] for k, v in
                     result.get("compared", {}).items() if v["value"]},
        "queries": lines.get("window", {}).get("queries"),
        "device": result.get("device"),
        "trace": lines.get("trace"),
        "breakdown": result.get("breakdown"),
    }
    print(json.dumps(summary), flush=True)
    return summary


def off_manifest(workload: str, off_config: str, out_dir: str) -> str:
    """A manifest beside the real one with the cell `<workload>-off`:
    the cell's traffic under another configuration."""
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {w["name"]: w for w in m["workloads"]}[workload]
    name = workload + "-off"
    m["workloads"].append(dict(cell, name=name, config=off_config))
    for x in m["per_layer"]:
        if workload in x.get("workloads", []):
            x["workloads"].append(name)
    shutil.copy(os.path.join(ROOT, "benchmark", "limits", workload + ".json"),
                os.path.join(ROOT, "benchmark", "limits", name + ".json"))
    path = os.path.join(out_dir, "manifest-off.json")
    json.dump(m, open(path, "w"))
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="51")
    ap.add_argument("--off-config", default=None)
    ap.add_argument("--traced", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU at the tiny scale: tries the script")
    ap.add_argument("--keep-cache", action="store_true",
                    help="do not empty the compile cache first")
    args = ap.parse_args(argv)
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    if not args.keep_cache:
        shutil.rmtree(cache, ignore_errors=True)
    env = dict(os.environ, JAX_LOG_COMPILES="1")
    seeds = args.seeds.split(",")
    common = ["--seconds", args.seconds] + (
        ["--rehearse"] if args.rehearse else [])
    steps = [("cold", ["--workload", args.workload, "--seed", seeds[0],
                       "--trace", "0"])]
    if args.off_config:
        steps.append(("off", [
            "--workload", args.workload + "-off", "--seed", seeds[0],
            "--trace", "0", "--manifest",
            off_manifest(args.workload, args.off_config, out_dir)]))
    steps += [(f"seed{k}", ["--workload", args.workload, "--seed", s,
                            "--trace", "0"])
              for k, s in enumerate(seeds[1:], 2)]
    if args.traced:
        steps.append(("traced", ["--workload", args.workload, "--seed",
                                 seeds[-1], "--trace", "1"]))
    try:
        done = [run_step(name, a + common, out_dir, env)
                for name, a in steps]
    finally:
        if args.off_config:
            os.remove(os.path.join(ROOT, "benchmark", "limits",
                                   args.workload + "-off.json"))
    json.dump(done, open(os.path.join(out_dir, "summary.json"), "w"),
              indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
