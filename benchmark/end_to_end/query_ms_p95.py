"""95th percentile of every query completed in the window (the last cut
point of `statistics.quantiles(n=20)`).  It wants some hundreds of
queries in a window, so only the cell whose query is short lists it;
under twenty samples there is no 95th percentile to report."""
import statistics


def read(ctx):
    times = [(r["end"] - r["asked"]) * 1e3 for r in ctx["records"]]
    if len(times) < 20:
        return None
    return statistics.quantiles(times, n=20, method="inclusive")[-1]
