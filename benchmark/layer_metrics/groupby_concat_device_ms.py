"""Layer: kernels.  The busiest chip's time in operations of the
program that makes a partition's partial-aggregate batches one batch
for the merge, `jit_agg_concat` (`columnar/batch.concat_batches` under
the aggregate's name: a gather at the capacity the merge then runs at,
`capacity_rows` of the `exec:groupby-merge` span), over the traced
slice, per traced query (`reduce/programs.py`).  With
`groupby_update_device_ms` and `groupby_merge_device_ms` it is the
grouped aggregate's share of the chip's busy time but for
`jit_agg_eval`, a few rows.  No such operation: nothing is read."""
from benchmark.reduce import programs as PG


def read(ctx):
    return PG.device_ms_per_query(ctx, "jit_agg_concat")
