"""The comparison that decides `correct`: one answer of the timed path
against the plain reference's, number by number.

`spec` is the reference module of the query (`KEYS`, `SORT`, `LIMIT`,
and `answer`, which returns every row in order, before the limit).
Every number has a limit of its own: the counts are exact (limit 0),
`float_rel_err` is held to the cell's limit (`benchmark/limits/`).
Ties: two float sort values that differ by less than the float limit
count as equal, in the order of the rows and at the cut of a top-N, so
rounding cannot turn a sound answer into a wrong one.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

COUNTS = ("rows_gap", "columns_wrong", "keys_unmatched",
          "exact_cells_wrong", "order_breaks", "topn_missed")
NUMBERS = COUNTS + ("float_rel_err",)


def plain(df: pd.DataFrame) -> pd.DataFrame:
    """Any engine's frame as float64 / int64 / str columns."""
    out = {}
    for c in df.columns:
        s = df[c]
        kind = getattr(s.dtype, "kind", "O")
        name = str(s.dtype)
        if kind == "f" or name.startswith("Float"):
            out[c] = s.to_numpy(dtype=np.float64, na_value=np.nan)
        elif kind in "iub" or name.startswith(("Int", "UInt", "bool")):
            out[c] = s.to_numpy(dtype=np.int64, na_value=-2 ** 62)
        else:
            out[c] = s.astype(object).map(
                lambda v: None if pd.isna(v) else str(v)).to_numpy(object)
    return pd.DataFrame(out)


def _tied(a: float, b: float, tol: float) -> bool:
    return abs(a - b) <= tol * max(abs(a), abs(b))


def _before(row_a, row_b, sort, tol) -> int:
    """-1 / 0 / +1: row_a sorts before / ties with / after row_b.  Two
    float values within the tolerance leave the order open: the side
    that rounded them apart never looked at the later sort columns."""
    for col, ascending in sort:
        a, b = row_a[col], row_b[col]
        if isinstance(a, float):
            if _tied(a, b, tol):
                return 0
        elif a == b:
            continue
        return -1 if (a < b) == ascending else 1
    return 0


def compare(got: pd.DataFrame, ref_all: pd.DataFrame, spec,
            float_limit: float) -> dict:
    """The numbers of one answer.  `ref_all` is `spec.answer(tables)`."""
    got, ref_all = plain(got), plain(ref_all)
    n = {k: 0 for k in COUNTS}
    n["float_rel_err"] = 0.0
    limit = spec.LIMIT
    want = ref_all if limit is None else ref_all.head(limit)
    n["rows_gap"] = abs(len(got) - len(want))
    if list(got.columns) != list(ref_all.columns):
        n["columns_wrong"] = 1
        return n
    floats = [c for c in ref_all.columns if ref_all[c].dtype.kind == "f"]
    exact = [c for c in ref_all.columns
             if c not in floats and c not in spec.KEYS]
    if spec.KEYS:
        index = {k: i for i, k in enumerate(
            ref_all[spec.KEYS].itertuples(index=False, name=None))}
        partner = [index.get(k) for k in
                   got[spec.KEYS].itertuples(index=False, name=None)]
    else:
        partner = [i if i < len(ref_all) else None
                   for i in range(len(got))]
    n["keys_unmatched"] = sum(p is None for p in partner)
    rows = [(i, p) for i, p in enumerate(partner) if p is not None]
    gi = [i for i, _ in rows]
    ri = [p for _, p in rows]
    for c in exact:
        n["exact_cells_wrong"] += int(
            (got[c].to_numpy()[gi] != ref_all[c].to_numpy()[ri]).sum())
    for c in floats:
        g = got[c].to_numpy()[gi]
        e = ref_all[c].to_numpy()[ri]
        with np.errstate(divide="ignore", invalid="ignore"):
            err = np.where(g == e, 0.0, np.abs(g - e) / np.abs(e))
        err = np.where(np.isfinite(err), err, np.inf)
        if len(err):
            n["float_rel_err"] = max(n["float_rel_err"], float(err.max()))
    records = got.to_dict("records")
    n["order_breaks"] = sum(
        _before(a, b, spec.SORT, float_limit) > 0
        for a, b in zip(records, records[1:]))
    if limit is not None and records and len(ref_all) > len(want):
        last = records[-1]
        held = set(ri)
        for p in range(len(want)):
            if p not in held and _before(
                    ref_all.iloc[p].to_dict(), last, spec.SORT,
                    float_limit) < 0:
                n["topn_missed"] += 1
    return n


def worst(all_numbers: list) -> dict:
    """Number by number, the worst over the answers compared."""
    return {k: max((x[k] for x in all_numbers), default=0)
            for k in NUMBERS}


def verdict(numbers: dict, float_limit: float) -> tuple:
    """(correct, {name: [number, limit]})"""
    limits = {k: 0 for k in COUNTS}
    limits["float_rel_err"] = float_limit
    table = {k: [numbers[k], limits[k]] for k in NUMBERS}
    ok = all(v <= lim for v, lim in table.values())
    return ok, table
