"""TPC-H Q1, pricing summary report, in plain pandas from the query's
text:

    select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
      sum(l_extendedprice) as sum_base_price,
      sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
      sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
      avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
      avg(l_discount) as avg_disc, count(*) as count_order
    from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus
    order by l_returnflag, l_linestatus
"""
import datetime as _dt

import pandas as pd

READS = {"lineitem": ["l_shipdate", "l_returnflag", "l_linestatus",
                      "l_quantity", "l_extendedprice", "l_discount",
                      "l_tax"]}
KEYS = ["l_returnflag", "l_linestatus"]
SORT = [("l_returnflag", True), ("l_linestatus", True)]
LIMIT = None


def _days(s):
    return (_dt.date.fromisoformat(s) - _dt.date(1970, 1, 1)).days


def answer(t) -> pd.DataFrame:
    li = t["lineitem"]
    li = li[li.l_shipdate <= _days("1998-09-02")]
    disc_price = li.l_extendedprice * (1.0 - li.l_discount)
    rows = pd.DataFrame({
        "l_returnflag": li.l_returnflag, "l_linestatus": li.l_linestatus,
        "qty": li.l_quantity, "price": li.l_extendedprice,
        "disc_price": disc_price, "charge": disc_price * (1.0 + li.l_tax),
        "disc": li.l_discount})
    out = rows.groupby(KEYS, sort=True).agg(
        sum_qty=("qty", "sum"), sum_base_price=("price", "sum"),
        sum_disc_price=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("qty", "mean"), avg_price=("price", "mean"),
        avg_disc=("disc", "mean"), count_order=("qty", "size"))
    return out.reset_index()
