"""Layer: kernels.  The queries' memory roofline: the least time the
cell's chips could take to move the bytes the traced queries must move
(`reduce/least_bytes.py`) at the published HBM rate
(`reduce/peaks.py`), over the busiest chip's busy time in the traced
slice.  Bound by bytes: these operators do next to no arithmetic per
byte."""
from benchmark.reduce.peaks import peaks_of


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr.get("traced"):
        return None
    least = [ctx["queries"][q]["least_bytes"] for q in tr["traced"]]
    if not all(least):
        return None
    rate = peaks_of(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * sum(least) / (rate * ctx["chips"]) / tr["busy_s_busiest"]
