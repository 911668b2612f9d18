"""Layer: scan + upload.  The bytes a query must read, at storage width
(`reduce/least_bytes.py`, as `hbm_roofline_pct` takes them), over the
time its `exec:SourceUpload[s<k>]` spans took: the rate at which the
host hands the device its input.  Median over the traced queries; read
only where every traced query has its `bench:accelerate` span in the
slice, so that the two lists pair up in time order."""
import statistics

from benchmark.reduce import spans as SP


def read(ctx):
    traced = (ctx.get("trace") or {}).get("traced") or []
    queries = SP.by_query(ctx, SP.ACCELERATE, "exec:SourceUpload[")
    if not traced or len(queries) != len(traced):
        return None
    rates = []
    for q, (_, up) in zip(traced, queries):
        least = ctx["queries"][q]["least_bytes"]
        if up and least:
            rates.append(least / SP.total(up))    # bytes/ns = GB/s
    return statistics.median(rates) if rates else None
