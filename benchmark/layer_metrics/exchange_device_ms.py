"""Layer: kernels.  The busiest chip's time in operations of the
exchange's programs (`jit_exchange_split`, `jit_exchange_cut`, ...)
over the traced slice, per traced query (`reduce/programs.py`).  No
such operation: nothing is read."""
from benchmark.reduce import programs as PG


def read(ctx):
    return PG.device_ms_per_query(ctx, "jit_exchange_")
