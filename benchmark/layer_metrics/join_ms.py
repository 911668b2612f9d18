"""Layer: operators.  Host time of a query's joins: what the program's
`exec:join-build` and `exec:join-probe` spans own of the query's
`bench:collect` (`reduce/phases.py`: every instant belongs to the phase
span opened last), median over the traced queries.  The blocking
`join.expand` readbacks live here.  A program without the spans reads
nothing."""
from benchmark.reduce import phases as PH


def read(ctx):
    return PH.family_ms(ctx, "exec:join-")
