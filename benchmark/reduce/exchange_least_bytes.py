"""The least bytes a query's hash exchanges must send from one chip to
another: for each exchange the query's text implies (a join's two sides
repartitioned on the join key), the rows that reach it times the least
width of the columns the rest of the query still needs of them, times
(n - 1) / n: with n chips and rows spread evenly by a hash, that share
of them lies on another chip than the one their key sends them to.  It
is worked out from the tables and the query's text, the same whatever
implements the exchange (the padded blocks of an all-to-all, a pull of
slices), so a later PR that packs, compresses or fuses the collective
leaves the yardstick standing.  Imports nothing of the program.

Widths are the tables' own (`pandas` dtypes): int64 / float64 8 bytes,
int32 / DATE32 4.  A query is here once a cell needs it.
"""
from __future__ import annotations

import datetime as _dt

import pandas as pd


def _days(s: str) -> int:
    return (_dt.date.fromisoformat(s) - _dt.date(1970, 1, 1)).days


def _width(df: pd.DataFrame, columns) -> int:
    return int(sum(df[c].dtype.itemsize for c in columns))


def tpch_q3(t: dict) -> list:
    """Q3's four exchanges, in plan order: customer (filtered on the
    segment) and orders (filtered on the date) on the customer key; their
    join and lineitem (filtered on the ship date) on the order key.
    [(name, rows, bytes a row)]."""
    cust = t["customer"]
    cust = cust[cust.c_mktsegment == "BUILDING"]
    orders = t["orders"]
    orders = orders[orders.o_orderdate < _days("1995-03-15")]
    li = t["lineitem"]
    li = li[li.l_shipdate > _days("1995-03-15")]
    joined = orders[orders.o_custkey.isin(cust.c_custkey)]
    return [
        ("customer", len(cust), _width(cust, ["c_custkey"])),
        ("orders", len(orders), _width(orders, [
            "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"])),
        ("customer-orders", len(joined), _width(orders, [
            "o_orderkey", "o_orderdate", "o_shippriority"])),
        ("lineitem", len(li), _width(li, [
            "l_orderkey", "l_extendedprice", "l_discount"])),
    ]


EXCHANGES = {("tpch", 3): tpch_q3}


def query_cross_chip_bytes(suite: str, query: int, tables: dict,
                           chips: int) -> float:
    """Least bytes one run of the query sends between `chips` chips."""
    sent = sum(rows * width for _, rows, width in
               EXCHANGES[(suite, int(query))](tables))
    return sent * (chips - 1) / chips


def per_source_row(config: dict, query: int, reads: dict, chips: int,
                   sample_scale: int = 300_000) -> float:
    """The same, per source row the query reads.  A reader's `ctx` holds
    a run's row counts but neither its tables nor its seed, so the share
    of rows that pass each filter is read from the configuration's own
    generator at a sample scale and a fixed seed; they are properties
    of the generator's distributions (date ranges, one segment of five),
    not of the seed or the scale (a sample of 300,000 lineitem rows
    reads them to about 2%)."""
    import importlib
    scale = min(int(config["scale"]), int(sample_scale))
    tables = importlib.import_module(config["generator"]).generate(
        0, scale, list(reads))
    rows = sum(len(tables[t]) for t in reads)
    return query_cross_chip_bytes(config["suite"], query, tables,
                                  chips) / rows
