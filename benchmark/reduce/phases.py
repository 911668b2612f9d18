"""A query's host time split between the program's phase spans.

The program marks one span per exec, partition and phase
(`utils/profile.py`): `exec:join-build`, `exec:join-probe`,
`exec:exchange-write`, `exec:exchange-read`, `exec:groupby-update`,
`exec:groupby-merge`.  They are intervals of a pull pipeline, so they
nest (a group-by's update pulls the join's probe stream, which pulls an
exchange's reader, whose first pull runs the whole map side) and, with
the prefetch threads, overlap; their plain sum counts the same second
several times.  Here every instant of a query's `bench:collect` belongs
to one phase span at most: of the phase spans open over it, the one
opened last (the innermost of a nest; of two threads, the newer).  What
a family's spans own, per query, never adds up to more than the query.
"""
from __future__ import annotations

from benchmark.reduce import spans as SP

#: every family takes part in the split, whichever one is read
FAMILIES = ("exec:join-", "exec:exchange-", "exec:groupby-")


def owned_ns(spans: list, lo: float, hi: float) -> dict:
    """{family: ns} of [lo, hi) owned by each family's spans.  `spans`
    are (name, start_ns, end_ns)."""
    inside = [(s, e, f) for n, s, e in spans for f in FAMILIES
              if n.startswith(f) and s >= lo and e <= hi and e > s]
    cuts = sorted({t for s, e, _ in inside for t in (s, e)})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        open_now = [(s, f) for s, e, f in inside if s <= a and e >= b]
        if open_now:
            fam = max(open_now)[1]
            out[fam] = out.get(fam, 0) + (b - a)
    return out


def family_ms(ctx: dict, family: str):
    """Median over the traced queries of what `family`'s spans own of
    the query's `bench:collect`; a query without such a span is left
    out, and with none at all nothing is read."""
    spans = (ctx.get("planes") or {}).get("spans") or []
    per_query = [owned_ns(spans, lo, hi).get(family)
                 for (lo, hi), mine in SP.by_query(ctx, SP.COLLECT, family)
                 if mine]
    return SP.median_ms([v for v in per_query if v])
