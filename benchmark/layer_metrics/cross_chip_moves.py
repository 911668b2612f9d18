"""Layer: operators.  Counted moves of batches from one chip to another
outside the all-to-all, a query: the program counts one
(`utils/checks.cross_chip_moves`) and opens one `exec:to-one-chip` span
at the same site (`parallel/mesh.to_one_chip`) every time a plan's
single-partition point finds a batch on another chip; read here as the
spans inside a query's `bench:accelerate` and `bench:collect`, median
over the traced queries (a traced rehearsal on virtual devices reads
it too: an exact count).  A program without the span reads nothing."""
import statistics

from benchmark.reduce import spans as SP

SPAN = "exec:to-one-chip"


def read(ctx):
    collects = SP.by_query(ctx, SP.COLLECT, SPAN)
    plans = SP.by_query(ctx, SP.ACCELERATE, SPAN)
    if not any(mine for _, mine in collects + plans):
        return None
    counts = [len(mine) for _, mine in collects]
    for i, (_, mine) in enumerate(plans[:len(counts)]):
        counts[i] += len(mine)
    return statistics.median(counts)
