"""What a window looked like from inside, on an earlier line of every
run, for whoever has to find why two runs of one cell differ: no
metric reads it but `gc_ms_per_query`."""
from __future__ import annotations

import time


class GcClock:
    """A `gc.callbacks` entry: seconds the garbage collector held the
    process, by generation, and its longest pause."""

    def __init__(self):
        self.seconds, self.runs, self.longest = [0.0] * 3, [0] * 3, 0.0
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        took = time.perf_counter() - self._t0
        g = min(int(info.get("generation", 2)), 2)
        self.seconds[g] += took
        self.runs[g] += 1
        self.longest = max(self.longest, took)

    def read(self) -> dict:
        return {"seconds": list(self.seconds), "runs": list(self.runs),
                "longest_s": self.longest}


def shape(window: dict, seconds: float, gc_clock: GcClock,
          slices: int = 17) -> dict:
    """The spread of the query times; the slow ones (over 1.15 medians)
    by when they started, which call held them and the CPU seconds the
    process spent meanwhile (all threads: far under the wall time, the
    process stood still); every slice of the window with its count,
    median and slowest; the collector."""
    rec = sorted(window["records"], key=lambda r: r["end"])
    if not rec:
        return {}

    def ms(r):
        return (r["end"] - r["asked"]) * 1e3
    times = sorted(ms(r) for r in rec)

    def at(p):
        return times[min(len(times) - 1, int(p * len(times)))]
    mid = at(0.5)
    slow = [{"at_s": round(r["start"] - window["opened"], 2),
             "ms": round(ms(r), 1),
             "accelerate_ms": round((r["planned"] - r["start"]) * 1e3, 1),
             "cpu_s": round(r["cpu"] - before["cpu"], 3)}
            for before, r in zip(rec, rec[1:]) if ms(r) > 1.15 * mid]
    width = max(seconds, rec[-1]["end"] - window["opened"]) / slices
    by_slice = [[] for _ in range(slices)]
    for r in rec:
        i = int((r["end"] - window["opened"]) / width)
        by_slice[min(max(i, 0), slices - 1)].append(ms(r))
    timeline = [[len(got), round(sorted(got)[len(got) // 2], 1),
                 round(max(got), 1)] if got else [0] for got in by_slice]
    return {"queries": len(times), "min": times[0], "p50": mid,
            "p90": at(0.9), "p95": at(0.95), "p99": at(0.99),
            "max": times[-1], "slow_queries": len(slow), "slow": slow[:40],
            "slices_n_p50_max": timeline, "gc": gc_clock.read()}
