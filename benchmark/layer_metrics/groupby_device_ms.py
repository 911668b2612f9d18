"""Layer: kernels.  The busiest chip's time in operations of the
aggregate's programs (`jit_agg_update`, `jit_agg_merge`, `jit_agg_eval`,
the dictionary and reduce lanes: whatever lane was taken) over the
traced slice, per traced query (`reduce/programs.py`).  No such
operation: nothing is read."""
from benchmark.reduce import programs as PG


def read(ctx):
    return PG.device_ms_per_query(ctx, "jit_agg_")
