"""Layer: kernels.  The busiest chip's time in operations of the
program that groups partial-aggregate rows, `jit_agg_merge` (the key
sort and segmented scan again: over a partition's concatenated
partials, at `capacity_rows` of the `exec:groupby-merge` span, and over
the exchanged partials in the final aggregate), over the traced slice,
per traced query (`reduce/programs.py`).  What `jit_agg_concat` takes
is `groupby_concat_device_ms`'s; `jit_agg_eval` is in none of the
three: with the other operators' programs it is the rest of the chip's
busy time.  No such operation: nothing is read."""
from benchmark.reduce import programs as PG


def read(ctx):
    return PG.device_ms_per_query(ctx, "jit_agg_merge")
