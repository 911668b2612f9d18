"""Layer: device.  1 - (union of the busiest chip's operation
intervals) / (traced slice of the window)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s_busiest"] / tr["window_s"])
