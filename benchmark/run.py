#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's entry in `BENCHMARK.json` names its configuration and its
traffic mix; `benchmark/manifest.py` finds the rest by name.  A run
makes the tables from `--seed`, drives each query of the mix through
`collect(accelerate(plan, conf), conf)` once (tracing, compiling or
loading the compile cache) and then until a repeat asks the compiler
for nothing, and calls that set-up.  The window is the traffic file's
(`benchmark/load.py`: the mix's queries in an order drawn from the
seed, a closed or an open loop, for `--seconds`).  Once it has closed
and the peak of device memory is read, the plain reference answers
each query once and every answer of the window is compared with its
own (`benchmark/compare.py`).  The last line of standard output is the
result.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and runs nothing.  `--rehearse` runs the configuration's tiny
`rehearse_scale` on the CPU instead, says so, and reports counts only:
no time of a CPU run goes out under a metric's name.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare as CMP  # noqa: E402
from benchmark import manifest as MF  # noqa: E402
from benchmark import precision as PRC  # noqa: E402
from benchmark.reduce import least_bytes as LB  # noqa: E402
from benchmark.reduce import window as WIN  # noqa: E402

#: where a traced run keeps its profile until it is reduced
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def say(**what) -> None:
    """An earlier line of standard output: one JSON object."""
    print(json.dumps(what), flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny scale on the CPU; counts only")
    ap.add_argument("--control", choices=sorted(PRC.RUNGS), default=None,
                    help="compare the reference at this lower precision "
                         "in the program's place; has to read not correct")
    ap.add_argument("--manifest", default=None, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def find_devices(cell, rehearse: bool):
    """The chips, or no run.  Rehearsal pins JAX to the CPU before JAX
    is imported and gives it as many virtual devices as the cell has
    chips."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{max(cell.chips, 1)}").strip()
    import jax
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise SystemExit(
                f"benchmark: needs a TPU, JAX found {devs[0].platform!r} "
                f"({devs[0].device_kind}); nothing was run")
        from benchmark.reduce.peaks import peaks_of
        peaks_of(devs[0].device_kind)
    if len(devs) < cell.chips:
        raise SystemExit(f"benchmark: {cell.name} needs {cell.chips} "
                         f"chips, JAX found {len(devs)}; nothing was run")
    return devs


def peak_bytes(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


class Tracer:
    """Profiles the first queries of the window into TRACE_DIR/<cell>,
    up to `queries` of them or `seconds` of tracing, whichever comes
    first (stopping and reading a trace costs tens of seconds a traced
    second), then reads the trace, reduces it and deletes it."""

    def __init__(self, cell, queries: int, seconds: float):
        self.dir = os.path.join(TRACE_DIR, cell.name)
        self.limit, self.seconds = queries, seconds
        self.traced = []            # the queries profiled, by number
        self.spent = {}             # what stopping and reading cost
        self.on = False
        self.lock = threading.Lock()
        self.planes = {}
        self.reduced = {}

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False   # the HLO text is most of a trace
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = time.perf_counter()
        self.on = True

    def annotate(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name) if self.on \
            else contextlib.nullcontext()

    def after_query(self, query: int) -> None:
        with self.lock:
            if self.on:
                self.traced.append(query)
                if len(self.traced) >= self.limit or \
                        time.perf_counter() - self.started >= self.seconds:
                    self._stop()

    def stop(self) -> None:
        with self.lock:
            self._stop()

    def _stop(self) -> None:
        import jax
        if self.on:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self.spent["stop_trace_s"] = time.perf_counter() - t0
            self.on = False

    def reduce(self) -> dict:
        from benchmark.reduce import trace as TR
        try:
            t0 = time.perf_counter()
            path = TR.newest_xplane(self.dir)
            self.spent["trace_bytes"] = os.path.getsize(path)
            self.planes = TR.read_planes(path)
            self.reduced = TR.reduce_planes(self.planes)
            self.spent["read_and_reduce_s"] = time.perf_counter() - t0
            if self.reduced:
                self.reduced["queries"] = len(self.traced)
                self.reduced["traced"] = list(self.traced)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.reduced


def run_cell(args, engine_factory=None) -> dict:
    """The whole of a run but the printing of its last line.  Tests pass
    an `engine_factory` to break the timed path underneath."""
    cell = MF.Cell(MF.load(args.manifest), args.workload)
    devs = find_devices(cell, args.rehearse)
    import jax
    from benchmark import engine as EN
    config, traffic, refs = cell.config, cell.traffic, cell.references
    driver = importlib.import_module(traffic.get("driver", "benchmark.load"))
    seconds = args.seconds if args.seconds is not None \
        else float(cell.manifest["run_seconds"])
    scale = int(config["rehearse_scale"] if args.rehearse
                else config["scale"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(start={"workload": cell.name, "seed": args.seed, "scale": scale,
               "seconds": seconds, "trace": args.trace,
               "rehearse": args.rehearse, "device": device,
               "jax": jax.__version__})

    counter = EN.CompileCounter()
    engine = (engine_factory or EN.Engine)(config, bool(args.trace))
    t0 = time.perf_counter()
    generator = importlib.import_module(config["generator"])
    reads = cell.reads()
    tables = generator.generate(args.seed, scale, list(reads))
    say(data={"generator": config["generator"], "seed": args.seed,
              "rows": {k: int(len(v)) for k, v in tables.items()},
              "gen_s": time.perf_counter() - t0,
              "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    engine.register(tables)
    tracer = Tracer(cell, int(traffic.get("trace_queries", 3)),
                    float(traffic.get("trace_seconds", 5.0)))

    with engine.session():
        # ---- set-up: the first run of each query, then warm-up ---------
        first = {"first_query_s": 0.0, "compile_requests": 0,
                 "cache_hits": 0}
        for q in cell.queries:
            c0, h0 = counter.requests, counter.hits
            _, clk = engine.run(q)
            first["first_query_s"] += clk[2] - clk[0]
            first["compile_requests"] += counter.requests - c0
            first["cache_hits"] += counter.hits - h0
        warm = []
        for _ in range(int(traffic.get("warmup_max", 4))):
            c1, t1 = counter.requests, time.perf_counter()
            for q in cell.queries:
                engine.run(q)
            warm.append({"s": time.perf_counter() - t1,
                         "compile_requests": counter.requests - c1})
            if counter.requests == c1:
                break
        # The collector's oldest generation is walked whole at every
        # full collection: set-up's heap (modules, compiled programs)
        # cost q6 a 95 ms pause every 33rd query.  A service collects
        # and freezes what start-up left, and so does the benchmark;
        # what the window's own garbage costs stays in the window.
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - T_START
        say(setup={"setup_s": setup_s, "first": first, "warm_up": warm,
                   "gc_frozen": gc.get_freeze_count()})

        # ---- the window: the traffic file's, by its generator of load --
        if args.trace:
            tracer.start()
        c_open = counter.requests
        gc_clock = WIN.GcClock()
        gc.callbacks.append(gc_clock)
        window = driver.drive(engine, traffic, args.seed, seconds, tracer)
        gc.callbacks.remove(gc_clock)
        tracer.stop()
        compiles_in_window = counter.requests - c_open
        shard_devices = engine.mesh_shard_devices()
    records, answers = window["records"], window["answers"]
    peak = peak_bytes(devs[:cell.chips])
    say(window=WIN.shape(window, seconds, gc_clock))
    if compiles_in_window:
        say(WARNING_COMPILED_INSIDE_THE_WINDOW=compiles_in_window)
        print(f"benchmark: {compiles_in_window} XLA COMPILE REQUESTS "
              "INSIDE THE MEASURED WINDOW", file=sys.stderr)

    # ---- the trace, reduced in-process ---------------------------------
    reduced = tracer.reduce() if args.trace else {}
    if args.trace:
        say(trace=dict(tracer.spent, queries=len(tracer.traced)))

    # ---- correct: the references, once the window has closed -----------
    engine.release()
    del engine
    gc.unfreeze()
    gc.collect()
    t0 = time.perf_counter()
    ref_all = {q: refs[q].answer(tables) for q in cell.queries}
    ref_s = time.perf_counter() - t0
    limit = float(cell.limits["float_rel_err"])
    compared = [(q, PRC.control_answer(refs[q], tables, args.control))
                for q in cell.queries] if args.control else answers
    per_answer = [CMP.compare(a, ref_all[q], refs[q], limit)
                  for q, a in compared]
    wrong = sum(not CMP.verdict(n, limit)[0] for n in per_answer)
    ok, table = CMP.verdict(CMP.worst(per_answer), limit)
    attempted = len(answers) + window["errors"]
    failed = window["errors"] + wrong
    correct = bool(ok and per_answer and failed == 0)
    say(reference={"modules": [refs[q].__name__ for q in cell.queries],
                   "reference_s": ref_s,
                   "answers_compared": len(per_answer),
                   "control": args.control})

    def result_of(q):
        return ref_all[q] if refs[q].LIMIT is None \
            else ref_all[q].head(refs[q].LIMIT)
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "records": records, "window": window, "setup_s": setup_s,
           "first": first, "peak_bytes": peak, "device": device,
           "chips": cell.chips, "shard_devices": shard_devices,
           "compiles_in_window": compiles_in_window,
           "gc": gc_clock.read(),
           "trace": reduced, "planes": tracer.planes,
           "queries": {q: {
               "rows": LB.rows_read(tables, refs[q].READS),
               "least_bytes": LB.query_least_bytes(
                   tables, refs[q].READS, result_of(q))
               if args.trace else None} for q in cell.queries}}
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = cell.read_metrics(kind, ctx)
    if args.rehearse:
        counts = {m["name"] for m in cell.metrics(kind)
                  if m["source"] == "program_counter"}
        metrics = {k: v for k, v in metrics.items() if k in counts}
    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace and reduced:
        device["busy_s"] = reduced["busy_s_mean"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["workload"] = cell.name
    result["seed"] = args.seed
    if args.rehearse:
        result["rehearsed_on"] = device["platform"]
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in table.items()}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    result = run_cell(args)
    sys.stdout.flush()
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
