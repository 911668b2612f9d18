"""Rows of every source table the completed queries read, over the time
from the first query's start to the last one's end: all the work over
all the time, so a stall inside the window shows."""


def read(ctx):
    rec = ctx["records"]
    if not rec:
        return None
    rows = sum(ctx["queries"][r["query"]]["rows"] for r in rec)
    return rows / (max(r["end"] for r in rec)
                   - min(r["asked"] for r in rec))
