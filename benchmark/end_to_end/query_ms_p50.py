"""Median over every query completed in the window, client's clock from
when the answer was asked for to the answer materialised on the
host."""
import statistics


def read(ctx):
    times = [(r["end"] - r["asked"]) * 1e3 for r in ctx["records"]]
    return statistics.median(times) if times else None
