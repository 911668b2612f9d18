"""Layer: kernels.  The mesh exchange's inter-chip roofline: the least
time the cell's chips could take to send between them the bytes the
traced queries' hash exchanges must send
(`reduce/exchange_least_bytes.py`: rows that reach each exchange x the
least width of the columns still needed x (n - 1) / n) at the published
inter-chip rate (`reduce/ici_peaks.py`, a chip's links together, n
chips sending at once), over the busiest chip's time in the
`jit_mesh_` programs in the traced slice.  Expect well under 1%: those
programs' time is their split and scatter, not the wire, and the number
says so."""
from benchmark.reduce import exchange_least_bytes as XB
from benchmark.reduce import programs as PG
from benchmark.reduce.ici_peaks import ici_peak_of


def read(ctx):
    tr = ctx.get("trace") or {}
    per_query_ms = PG.device_ms_per_query(ctx, "jit_mesh_")
    chips = int(ctx.get("chips") or 0)
    if not per_query_ms or chips < 2 or not tr.get("traced"):
        return None
    cell = ctx["cell"]
    if any((ctx["config"]["suite"], int(q)) not in XB.EXCHANGES
           for q in tr["traced"]):
        return None
    a_row = {q: XB.per_source_row(ctx["config"], q,
                                  cell.references[q].READS, chips)
             for q in set(tr["traced"])}
    least = sum(ctx["queries"][q]["rows"] * a_row[q] for q in tr["traced"])
    rate = ici_peak_of(ctx["device"]["kind"])["ici_bytes_per_s"]
    busy_s = per_query_ms * tr["queries"] / 1e3
    return 100.0 * least / (rate * chips) / busy_s
