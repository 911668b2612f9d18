"""Layer: kernels.  The busiest chip's time in operations of the
grouped aggregate's per-batch program `jit_agg_update` (pre-stage
filter and projection, key sort, stacked gather, segmented scan) over
the traced slice, per traced query (`reduce/programs.py`).  With
`groupby_merge_device_ms` it is most of `groupby_device_ms`; the rest
of the chip's busy time in a group-by query is `jit_agg_concat`,
`jit_agg_eval`, the exchange's and the sort's programs and the upload's
split.  No such operation: nothing is read."""
from benchmark.reduce import programs as PG


def read(ctx):
    return PG.device_ms_per_query(ctx, "jit_agg_update")
