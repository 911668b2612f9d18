"""Layer: scan + upload.  The `exec:upload-put` spans of a query (one a
partition, around `ColumnarBatch.from_numpy` of each of its chunks: pad
to the capacity bucket and `device_put` data and validity), summed,
median over the traced queries.  `upload_ms` less this is the
pandas-to-numpy conversion (`exec:upload-convert`)."""
from benchmark.reduce import spans as SP


def read(ctx):
    return SP.median_ms([SP.total(put) for _, put in SP.by_query(
        ctx, SP.ACCELERATE, "exec:upload-put") if put])
