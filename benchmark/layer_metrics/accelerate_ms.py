"""Layer: entry + planner.  Median of the benchmark's own span around
`accelerate()` (rewrite, fusion, source upload), apart from
`collect()`."""
import statistics


def read(ctx):
    spans = [(r["planned"] - r["start"]) * 1e3 for r in ctx["records"]]
    return statistics.median(spans) if spans else None
