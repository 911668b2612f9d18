"""Layer: operators.  Host time of a query's grouped aggregates: what
the program's `exec:groupby-update` and `exec:groupby-merge` spans own
of the query's `bench:collect` (`reduce/phases.py`: every instant
belongs to the phase span opened last), median over the traced queries.
Where the update loop waits on nothing it is the time to dispatch its
batches, and the device's queue drains under the merge.  A program
without the spans reads nothing."""
from benchmark.reduce import phases as PH


def read(ctx):
    return PH.family_ms(ctx, "exec:groupby-")
