"""Layer: device.  How evenly the cell's chips work: the least busy
chip's busy time over the busiest's in the traced slice
(`reduce/trace.reduce_planes`: `busy_s_by_chip`, the union of each
chip's operation intervals).  100 = every chip as busy as the busiest;
a few per cent = one chip does the query and the others run the
collective alone.  One chip, or no trace: nothing is read."""


def read(ctx):
    by_chip = (ctx.get("trace") or {}).get("busy_s_by_chip") or {}
    if len(by_chip) < 2 or max(by_chip.values()) <= 0:
        return None
    return 100.0 * min(by_chip.values()) / max(by_chip.values())
