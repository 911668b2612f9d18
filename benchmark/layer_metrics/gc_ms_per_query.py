"""Milliseconds a query of the window spent in Python's garbage
collector, all generations, by the benchmark's own clock around each
collection (`gc.callbacks`): what the planner's and the operators'
short-lived objects cost once set-up's heap is frozen."""


def read(ctx):
    rec = ctx["records"]
    if not rec or "gc" not in ctx:
        return None
    return sum(ctx["gc"]["seconds"]) * 1e3 / len(rec)
