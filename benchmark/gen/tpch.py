"""TPC-H tables from a seed, for the benchmark alone.

The tables of the TPC-H specification (rev 3, clause 4.2.3 and dbgen):
dbgen's row counts for a scale factor, the full column list of every
table under the names and types `spark_rapids_tpu/models/tpch_data.
SCHEMAS` gives them, the specification's value ranges and the rules
that tie columns together (1 to 7 lines an order, numbered; an extended
price that is quantity times the part's retail price; flags that follow
the dates).  Only the tables asked for are made, and every string
column is built with array operations (a lookup into a small table of
phrases, or `np.char` on whole columns) instead of one Python call a
row, because every run of every check pays for it.  It imports nothing
from the program.

`scale` is the number of lineitem rows; SF1 is 6,000,000.  Dates are
int32 days since 1970-01-01 (the engine's DATE32 storage).  Each table
draws from its own stream of the seed, so a table's rows do not depend
on which other tables a query asked for.

Where this departs from dbgen (the configuration files list the same
under `assumed`): the lineitem count is the scale itself, exactly
(dbgen's varies with its seed: 6,001,215 at SF1), so an order's line
counts are drawn in pairs of 4 + d and 4 - d; keys are dense and start
at 0 (dbgen's start at 1, its order keys use 8 of every 32);
`o_totalprice` and `o_orderstatus` are drawn, not summed over the
order's lines; text columns are short phrases of colour words with the
phrases some queries look for planted in a share of rows, names and
clerks are numbered; measures are float64 where TPC-H states
decimal(15,2).
"""
from __future__ import annotations

import datetime as _dt

import numpy as np
import pandas as pd
import pyarrow as pa

#: the dtype pandas itself gives a column of Python strings
_STR = pd.StringDtype(na_value=np.nan)

SF1_SCALE = 6_000_000
_EPOCH = _dt.date(1970, 1, 1)


def days(s: str) -> int:
    return (_dt.date.fromisoformat(s) - _EPOCH).days


REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIP_MODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
INSTRUCTIONS = ["COLLECT COD", "DELIVER IN PERSON", "NONE",
                "TAKE BACK RETURN"]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAIN_S1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAIN_S2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "burnished", "chartreuse", "chiffon", "chocolate", "coral",
          "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
          "dim", "dodger", "drab", "firebrick", "floral", "forest",
          "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey",
          "honeydew", "hot", "hotpink", "indian", "ivory", "khaki"]

TABLES = ("region", "nation", "supplier", "customer", "part", "partsupp",
          "orders", "lineitem")


#: dbgen's CURRENTDATE: a line received by then was returned or
#: accepted, one shipped after it is still open
CURRENT_DATE = days("1995-06-17")


def row_counts(scale: int) -> dict:
    """dbgen's rows for the scale factor `scale` / 6,000,000 (SF1:
    10,000 suppliers, 150,000 customers, 200,000 parts, 800,000
    partsupp rows, 1,500,000 orders), floored for tiny scales."""
    if scale < 20:
        raise ValueError("benchmark: a scale under 20 lineitem rows")
    n_part = max(scale // 30, 20)
    n_orders = min(max(scale // 4, 20), scale)
    return {"region": 5, "nation": len(NATIONS),
            "supplier": max(scale // 600, 5),
            "customer": max(scale // 40, 15), "part": n_part,
            "partsupp": n_part * 4, "orders": n_orders,
            "lineitem": scale}


def _lookup(options, codes) -> pd.api.extensions.ExtensionArray:
    """options[codes] as a pandas string column, decoded by Arrow from a
    dictionary: no Python object per row (an object array of six
    million strings costs the DataFrame constructor seconds)."""
    coded = pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(codes, dtype=np.int32)),
        pa.array(list(options), pa.large_string()))
    return pd.array(coded.dictionary_decode(), dtype=_STR)


def _pick(rng, options, n):
    return _lookup(options, rng.integers(0, len(options), n))


def _money(rng, lo, hi, n) -> np.ndarray:
    return np.round(rng.uniform(lo, hi, n), 2)


def _nation_keys(rng, n) -> np.ndarray:
    return rng.integers(0, len(NATIONS), n).astype(np.int64)


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """dbgen's p_retailprice of a part (its keys start at 1)."""
    k = np.asarray(partkey, dtype=np.int64) + 1
    return (90000 + (k // 10) % 20001 + 100 * (k % 1000)) / 100.0


def lines_per_order(rng, n_orders: int, n_lines: int) -> np.ndarray:
    """1 to 7 lines an order, `n_lines` in all: neighbours share eight
    lines as 4 + d and 4 - d, d uniform in -3..3, so every count from 1
    to 7 is as likely as dbgen's and the sum is fixed; what is left
    over goes to the first orders that have room."""
    d = rng.integers(-3, 4, n_orders // 2)
    counts = np.full(n_orders, 4, np.int64)
    counts[0:2 * len(d):2] += d
    counts[1:2 * len(d):2] -= d
    left = n_lines - int(counts.sum())
    step = 1 if left > 0 else -1
    while left:
        room = np.flatnonzero(counts < 7 if step > 0 else counts > 1)
        room = room[:abs(left)]
        if not len(room):
            raise ValueError(f"benchmark: {n_lines} lines do not fit "
                             f"{n_orders} orders of 1 to 7")
        counts[room] += step
        left -= step * len(room)
    return counts


def _comment(rng, n, specials=(), prefix: str = "", prefix_share=0.0):
    """'<colour> <colour> requests', by lookup into the 41 x 41 table of
    pairs.  Each phrase of `specials` is planted between the colours in
    about 8% of rows (a later phrase wins); `prefix` goes before a
    `prefix_share` of rows."""
    pairs = [(a, b) for a in COLORS for b in COLORS]
    table = [f"{a} {b} requests" for a, b in pairs]
    for phrase in specials:
        table += [f"{a} {phrase} {b} requests" for a, b in pairs]
    code = (rng.integers(0, len(COLORS), n) * len(COLORS)
            + rng.integers(0, len(COLORS), n))
    variant = np.zeros(n, np.int64)
    for i, _ in enumerate(specials):
        variant[rng.random(n) < 0.08] = i + 1
    code = code + variant * len(pairs)
    if prefix:
        code = code + (rng.random(n) < prefix_share) * len(table)
        table = table + [prefix + x for x in table]
    return _lookup(table, code)


def _numbered(prefix: str, ids: np.ndarray):
    """'<prefix>#000000123' for every id, as one array operation."""
    digits = np.char.zfill(ids.astype(np.int64).astype("U9"), 9)
    return pd.array(pa.array(np.char.add(prefix + "#", digits),
                             pa.large_string()), dtype=_STR)


def _phones(rng, n):
    parts = [rng.integers(10, 35, n), rng.integers(100, 999, n),
             rng.integers(100, 999, n), rng.integers(1000, 9999, n)]
    out = parts[0].astype("U2")
    for p in parts[1:]:
        out = np.char.add(np.char.add(out, "-"), p.astype("U4"))
    return pd.array(pa.array(out, pa.large_string()), dtype=_STR)


def _frame(columns: dict) -> pd.DataFrame:
    return pd.DataFrame(columns, copy=False)


def _order_dates(seed: int, n_orders: int) -> np.ndarray:
    """o_orderdate, from a stream of its own: lineitem's dates follow
    their order's, whether or not `orders` itself is generated."""
    rng = np.random.default_rng([_stream("o_orderdate"), seed])
    return rng.integers(days("1992-01-01"), days("1998-08-02"),
                        n_orders).astype(np.int32)


def _stream(name: str) -> int:
    return sum(ord(c) * (i + 1) for i, c in enumerate(name))


def _region(rng, n, counts, seed):
    return _frame({
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": _lookup(REGIONS, np.arange(5)),
        "r_comment": _comment(rng, 5)})


def _nation(rng, n, counts, seed):
    return _frame({
        "n_nationkey": np.arange(n, dtype=np.int64),
        "n_name": _lookup([x for x, _ in NATIONS], np.arange(n)),
        "n_regionkey": np.array([r for _, r in NATIONS], np.int64),
        "n_comment": _comment(rng, n)})


def _supplier(rng, n, counts, seed):
    return _frame({
        "s_suppkey": np.arange(n, dtype=np.int64),
        "s_name": _numbered("Supplier", np.arange(n)),
        "s_address": _comment(rng, n),
        "s_nationkey": _nation_keys(rng, n),
        "s_phone": _phones(rng, n),
        "s_acctbal": _money(rng, -999.99, 9999.99, n),
        "s_comment": _comment(rng, n, specials=["Customer", "Complaints"],
                              prefix="Customer Complaints ",
                              prefix_share=0.1)})


def _customer(rng, n, counts, seed):
    return _frame({
        "c_custkey": np.arange(n, dtype=np.int64),
        "c_name": _numbered("Customer", np.arange(n)),
        "c_address": _comment(rng, n),
        "c_nationkey": _nation_keys(rng, n),
        "c_phone": _phones(rng, n),
        "c_acctbal": _money(rng, -999.99, 9999.99, n),
        "c_mktsegment": _pick(rng, SEGMENTS, n),
        "c_comment": _comment(rng, n, specials=["special"])})


def _part(rng, n, counts, seed):
    names = [f"{a} {b} {c}" for a in COLORS[:12] for b in COLORS[12:26]
             for c in COLORS[26:]]
    names += ["forest " + x for x in names]
    name = (rng.integers(0, len(names) // 2, n)
            + (rng.random(n) < 0.05) * (len(names) // 2))
    types = [f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2
             for c in TYPE_S3]
    key = np.arange(n, dtype=np.int64)
    return _frame({
        "p_partkey": key,
        "p_name": _lookup(names, name),
        "p_mfgr": _pick(rng, [f"Manufacturer#{i}" for i in range(1, 6)], n),
        "p_brand": _pick(rng, [f"Brand#{i}{j}" for i in range(1, 6)
                               for j in range(1, 6)], n),
        "p_type": _pick(rng, types, n),
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": _pick(rng, [f"{a} {b}" for a in CONTAIN_S1
                                   for b in CONTAIN_S2], n),
        "p_retailprice": retail_price(key),
        "p_comment": _comment(rng, n)})


def _supp_of(partkey: np.ndarray, j: np.ndarray, n_supp: int) -> np.ndarray:
    """dbgen's supplier of a part's j-th offer (0-based keys)."""
    step = n_supp // 4 + partkey // n_supp
    return ((partkey + j * step) % n_supp).astype(np.int64)


def _partsupp(rng, n, counts, seed):
    partkey = np.repeat(np.arange(counts["part"], dtype=np.int64), 4)
    j = np.tile(np.arange(4, dtype=np.int64), counts["part"])
    return _frame({
        "ps_partkey": partkey,
        "ps_suppkey": _supp_of(partkey, j, counts["supplier"]),
        "ps_availqty": rng.integers(1, 10000, n).astype(np.int32),
        "ps_supplycost": _money(rng, 1.0, 1000.0, n),
        "ps_comment": _comment(rng, n)}).drop_duplicates(
            ["ps_partkey", "ps_suppkey"], ignore_index=True)


def _orders(rng, n, counts, seed):
    # dbgen gives no orders to a customer whose key divides by three
    buyers = np.flatnonzero((np.arange(counts["customer"]) + 1) % 3)
    return _frame({
        "o_orderkey": np.arange(n, dtype=np.int64),
        "o_custkey": buyers[rng.integers(0, len(buyers), n)
                            ].astype(np.int64),
        "o_orderstatus": _pick(rng, ["F", "O", "P"], n),
        "o_totalprice": _money(rng, 1000.0, 400000.0, n),
        "o_orderdate": _order_dates(seed, n),
        "o_orderpriority": _pick(rng, PRIORITIES, n),
        "o_clerk": _lookup([f"Clerk#{i:09d}" for i in range(1000)],
                           rng.integers(1, 1000, n)),
        "o_shippriority": np.zeros(n, np.int32),
        "o_comment": _comment(rng, n, specials=[
            "special", "pending", "deposits", "accounts"])})


def _lineitem(rng, n, counts, seed):
    n_orders = counts["orders"]
    per_order = lines_per_order(rng, n_orders, n)
    l_order = np.repeat(np.arange(n_orders, dtype=np.int64), per_order)
    first = np.repeat(np.cumsum(per_order) - per_order, per_order)
    odate = _order_dates(seed, n_orders)[l_order]
    l_ship = odate + rng.integers(1, 122, n).astype(np.int32)
    l_commit = odate + rng.integers(30, 91, n).astype(np.int32)
    l_receipt = l_ship + rng.integers(1, 31, n).astype(np.int32)
    l_part = rng.integers(0, counts["part"], n).astype(np.int64)
    qty = rng.integers(1, 51, n).astype(np.float64)
    returned = np.where(l_receipt <= CURRENT_DATE,
                        rng.integers(0, 2, n), 2)
    return _frame({
        "l_orderkey": l_order,
        "l_partkey": l_part,
        "l_suppkey": _supp_of(l_part, rng.integers(0, 4, n),
                              counts["supplier"]),
        "l_linenumber": (np.arange(n) - first + 1).astype(np.int32),
        "l_quantity": qty,
        "l_extendedprice": np.round(qty * retail_price(l_part), 2),
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": _lookup(["R", "A", "N"], returned),
        "l_linestatus": _lookup(["F", "O"], l_ship > CURRENT_DATE),
        "l_shipdate": l_ship,
        "l_commitdate": l_commit,
        "l_receiptdate": l_receipt,
        "l_shipinstruct": _pick(rng, INSTRUCTIONS, n),
        "l_shipmode": _pick(rng, SHIP_MODES, n),
        "l_comment": _comment(rng, n)})


_MAKERS = {"region": _region, "nation": _nation, "supplier": _supplier,
           "customer": _customer, "part": _part, "partsupp": _partsupp,
           "orders": _orders, "lineitem": _lineitem}


def generate(seed: int, scale: int, tables=TABLES) -> dict:
    """{table: DataFrame} for the tables asked for, a function of
    (seed, scale) alone."""
    counts = row_counts(scale)
    out = {}
    for name in tables:
        rng = np.random.default_rng([_stream(name), int(seed)])
        out[name] = _MAKERS[name](rng, counts[name], counts, int(seed))
    return out
