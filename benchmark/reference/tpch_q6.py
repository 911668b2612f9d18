"""TPC-H Q6, forecasting revenue change, in plain pandas from the
query's text:

    select sum(l_extendedprice * l_discount) as revenue from lineitem
    where l_shipdate >= date '1994-01-01'
      and l_shipdate < date '1994-01-01' + interval '1' year
      and l_discount between 0.06 - 0.01 and 0.06 + 0.01
      and l_quantity < 24
"""
import datetime as _dt

import pandas as pd

READS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                      "l_extendedprice"]}
KEYS = []
SORT = []
LIMIT = None


def _days(s):
    return (_dt.date.fromisoformat(s) - _dt.date(1970, 1, 1)).days


def answer(t) -> pd.DataFrame:
    li = t["lineitem"]
    keep = ((li.l_shipdate >= _days("1994-01-01"))
            & (li.l_shipdate < _days("1995-01-01"))
            & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
            & (li.l_quantity < 24))
    li = li[keep]
    return pd.DataFrame(
        {"revenue": [(li.l_extendedprice * li.l_discount).sum()]})
