"""Device time by program: the busiest chip's time in the operations of
the programs whose names start with a prefix, over the traced slice,
per traced query.

`ctx["planes"]["devices"]` holds (name, start_ns, end_ns) of every
operation a chip's core ran; `reduce/trace.read_planes` puts the name of
the executable that ran it in front (`jit_join_match/%fusion...`: the
program's `exec/base.named_jit` labels).  The slice is what
`reduce/trace.reduce_planes` takes: the first `bench:` span's start to
the last one's end.  The time is the union of the operations'
intervals, as the chip's busy time is: an operation that holds others
(a loop and its body) is on the line beside them, and their plain sum
read 0.5% over the busy time in a q3 (my chip run, PR 29 c2).  A core
runs one program at a time, so the metrics of disjoint prefixes add up
to no more than the chip's busy time.
"""
from __future__ import annotations

from benchmark.reduce import trace as TR


def device_ms_per_query(ctx: dict, prefix: str):
    trace, planes = ctx.get("trace") or {}, ctx.get("planes") or {}
    queries, by_chip = trace.get("queries"), trace.get("busy_s_by_chip")
    marks = [(s, e) for n, s, e in planes.get("spans") or []
             if n.startswith("bench:")]
    if not queries or not by_chip or not marks:
        return None
    lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    busiest = max(by_chip, key=by_chip.get)
    mine = [[s, e] for name, s, e in planes["devices"].get(busiest, ())
            if name.startswith(prefix)]
    ns = sum(e - s for s, e in TR.union(TR.clip(mine, lo, hi)))
    return ns / 1e6 / queries if ns > 0 else None
