"""Layer: operators.  Blocking device-to-host reads per query in the
window (`utils/checks.host_sync_count`): the median over its queries
where one client makes the count a query's own, else the window's count
over its queries.  An exact count."""
import statistics


def read(ctx):
    rec = ctx["records"]
    if not rec:
        return None
    if all("host_syncs" in r for r in rec):
        return statistics.median(r["host_syncs"] for r in rec)
    return ctx["window"]["host_syncs"] / len(rec)
