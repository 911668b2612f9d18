"""The least bytes a query must move through device memory: every
column its text reads, once, at the width the engine's storage model
gives it, plus its result.  It is worked out from the tables' shapes
and is blind to which kernels run, so a later PR that fuses or
replaces a kernel leaves the yardstick standing.

Widths: int64 / float64 8 bytes, int32 / DATE32 4, a string its UTF-8
bytes plus a 4-byte length.
"""
from __future__ import annotations

import pandas as pd


def _column_bytes(s: pd.Series) -> int:
    kind = getattr(s.dtype, "kind", "O")
    if kind in "iufb":
        return int(len(s) * s.dtype.itemsize)
    return int(s.str.encode("utf-8").str.len().sum() + 4 * len(s))


def frame_bytes(df: pd.DataFrame, columns=None) -> int:
    return sum(_column_bytes(df[c]) for c in (columns or df.columns))


def query_least_bytes(tables: dict, reads: dict, result: pd.DataFrame
                      ) -> int:
    """`reads` is the reference module's READS: {table: [columns]}."""
    return sum(frame_bytes(tables[t], cols) for t, cols in reads.items()
               ) + frame_bytes(result)


def rows_read(tables: dict, reads: dict) -> int:
    return sum(len(tables[t]) for t in reads)
