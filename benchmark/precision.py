"""The control of the comparison that decides `correct`: the plain
reference, computed in the nearest precision below the one a cell's
configuration states.  It stands in the program's place and has to come
out as not correct.

Every configuration states float64 columns and arithmetic, so the one
rung is `float32`: every float column cast to float32, the reference's
arithmetic then in float32.  A configuration that states another
precision brings its rung with it (bfloat16 for float32, and so on down
the contract's ladder).
"""
from __future__ import annotations

import numpy as np
import pandas as pd

RUNGS = {"float32": np.float32}


def lower(tables: dict, rung: str) -> dict:
    """The tables with every float column at the rung's precision."""
    dtype = RUNGS[rung]
    return {name: pd.DataFrame(
        {c: df[c].astype(dtype) if df[c].dtype.kind == "f" else df[c]
         for c in df.columns}, copy=False) for name, df in tables.items()}


def control_answer(reference, tables: dict, rung: str) -> pd.DataFrame:
    """What the control hands the comparison in the program's place:
    the reference module's answer over the lowered tables, cut to the
    query's limit as the program's answer is."""
    answer = reference.answer(lower(tables, rung))
    return answer if reference.LIMIT is None else answer.head(
        reference.LIMIT)
