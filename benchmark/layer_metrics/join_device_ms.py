"""Layer: kernels.  The busiest chip's time in operations of the join's
programs (`jit_join_match`, `jit_join_expand`, `jit_join_dense`,
`jit_join_semi`, ...) over the traced slice, per traced query
(`reduce/programs.py`).  No such operation: nothing is read."""
from benchmark.reduce import programs as PG


def read(ctx):
    return PG.device_ms_per_query(ctx, "jit_join_")
