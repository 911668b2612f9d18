"""Layer: exchange.  Host time of a query's exchanges: what the
program's `exec:exchange-write` (map side: split + cut, and the
operators under it that no phase span marks: filter, project, coalesce)
and `exec:exchange-read` spans own of the query's `bench:collect`
(`reduce/phases.py`), median over the traced queries.  A program
without the spans reads nothing."""
from benchmark.reduce import phases as PH


def read(ctx):
    return PH.family_ms(ctx, "exec:exchange-")
