"""Layer: scan + upload.  The `exec:upload-strings` spans of a query
(one an upload run, so one a partition unless it is past the transfer
budget; inside `exec:upload-put`, around the building and sending of
the run's string columns: a Python `encode` of every value, the scatter
into a `capacity x char_cap` byte matrix, three `jnp.asarray`s a chunk
and column), summed, median over the traced queries.  A program or a
source without the span reads nothing."""
from benchmark.reduce import spans as SP


def read(ctx):
    return SP.median_ms([SP.total(strings) for _, strings in SP.by_query(
        ctx, SP.ACCELERATE, "exec:upload-strings") if strings])
