"""Layer: entry + planner.  What `accelerate()` spends outside the
source upload: the benchmark's `bench:accelerate` span less the union
of the `exec:SourceUpload[s<k>]` spans inside it (rewrite, tagging,
fusion, coalesce insertion, and the query builder the benchmark calls
in the same span).  Median over the traced queries that have an upload
span; a program that records none gives nothing."""
from benchmark.reduce import spans as SP
from benchmark.reduce import trace as TR


def read(ctx):
    return SP.median_ms([(hi - lo) - SP.total(TR.union(up))
                         for (lo, hi), up in SP.by_query(
                             ctx, SP.ACCELERATE, "exec:SourceUpload[")
                         if up])
