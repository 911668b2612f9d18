"""Layer: kernels.  The busiest chip's time in operations of the mesh
exchange's programs (`jit_mesh_count`, `jit_mesh_exchange`: hash, split
by target chip, scatter into the send blocks, the all-to-all, the
compaction of what arrived) over the traced slice, per traced query
(`reduce/programs.py`).  No such operation: nothing is read."""
from benchmark.reduce import programs as PG


def read(ctx):
    return PG.device_ms_per_query(ctx, "jit_mesh_")
