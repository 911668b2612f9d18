"""TPC-H Q3, shipping priority, in plain pandas from the query's text:

    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
      o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
      and l_shipdate > date '1995-03-15'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate  limit 10

`answer` returns every group in that order; the comparison applies the
limit, so that two groups whose revenues tie at the cut to within the
float limit are not told apart by rounding.  Columns are in the order
the engine's plan emits them (group keys, then the aggregate).
"""
import datetime as _dt

import pandas as pd

READS = {"customer": ["c_mktsegment", "c_custkey"],
         "orders": ["o_custkey", "o_orderkey", "o_orderdate",
                    "o_shippriority"],
         "lineitem": ["l_orderkey", "l_shipdate", "l_extendedprice",
                      "l_discount"]}
KEYS = ["l_orderkey", "o_orderdate", "o_shippriority"]
SORT = [("revenue", False), ("o_orderdate", True)]
LIMIT = 10


def _days(s):
    return (_dt.date.fromisoformat(s) - _dt.date(1970, 1, 1)).days


def answer(t) -> pd.DataFrame:
    cust = t["customer"]
    cust = cust[cust.c_mktsegment == "BUILDING"][["c_custkey"]]
    orders = t["orders"]
    orders = orders[orders.o_orderdate < _days("1995-03-15")][
        ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]]
    li = t["lineitem"]
    li = li[li.l_shipdate > _days("1995-03-15")][
        ["l_orderkey", "l_extendedprice", "l_discount"]]
    j = cust.merge(orders, left_on="c_custkey", right_on="o_custkey")
    j = j.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    j["revenue"] = j.l_extendedprice * (1.0 - j.l_discount)
    out = j.groupby(KEYS, sort=False).agg(revenue=("revenue", "sum"))
    out = out.reset_index()
    return out.sort_values(["revenue", "o_orderdate"],
                           ascending=[False, True], kind="stable",
                           ignore_index=True)
