"""Layer: scan + upload.  The program's `exec:SourceUpload[s<k>]` spans
(plan/overrides._conv_source: host conversion and device_put of every
partition of one source, chunk by chunk), summed per query, median over
the traced queries.  A query with no such span is left out; with none
at all, nothing is read."""
from benchmark.reduce import spans as SP


def read(ctx):
    return SP.median_ms([SP.total(up) for _, up in SP.by_query(
        ctx, SP.ACCELERATE, "exec:SourceUpload[") if up])
