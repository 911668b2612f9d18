"""The program's host spans in a traced slice, by query.

`ctx["planes"]["spans"]` holds (name, start_ns, end_ns) of every host
event the trace reader kept, all threads in one list, on the profiler's
clock.  The benchmark wraps each query in `bench:accelerate` and
`bench:collect`; a span of the program belongs to the query whose
`bench:` span contains it.  That holds while one client runs: with
several, the `bench:` spans of different threads overlap and a span
cannot be assigned (PERF.md, Open questions)."""
from __future__ import annotations

import statistics

ACCELERATE = "bench:accelerate"
COLLECT = "bench:collect"


def by_query(ctx: dict, outer: str, prefix: str) -> list:
    """[((start, end) of an `outer` span, [(start, end) of each span
    whose name starts with `prefix` inside it])], in time order."""
    spans = (ctx.get("planes") or {}).get("spans") or []
    inner = [(s, e) for n, s, e in spans if n.startswith(prefix)]
    return [((lo, hi), [(s, e) for s, e in inner if s >= lo and e <= hi])
            for lo, hi in sorted((s, e) for n, s, e in spans if n == outer)]


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def median_ms(per_query_ns: list):
    """Median of per-query nanoseconds in ms; None with no query."""
    return statistics.median(per_query_ns) / 1e6 if per_query_ns else None
