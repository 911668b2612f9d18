"""Layer: operators.  The `exec:Readback` spans of a query, summed: the
host waiting for the device to drain what was dispatched ahead, the
stacked flag read (`TpuExec.collect`), and the conversion of the answer
to host rows (`plan/overrides._collect_to_host`).  Median over the
traced queries."""
from benchmark.reduce import spans as SP


def read(ctx):
    return SP.median_ms([SP.total(rb) for _, rb in SP.by_query(
        ctx, SP.COLLECT, "exec:Readback") if rb])
