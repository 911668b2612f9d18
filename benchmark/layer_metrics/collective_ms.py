"""Layer: exchange.  Host time of a query's mesh collectives: the
program's `exec:exchange-collective` spans (the count all-to-all, the
read of its n totals, the data all-to-all's dispatch; one an exchange,
one after the other under the whole-mesh dispatch gate) inside the
query's `bench:collect`, summed, median over the traced queries.  A
program without the span reads nothing."""
from benchmark.reduce import spans as SP


def read(ctx):
    per_query = [SP.total(mine) for _, mine in SP.by_query(
        ctx, SP.COLLECT, "exec:exchange-collective") if mine]
    return SP.median_ms(per_query)
