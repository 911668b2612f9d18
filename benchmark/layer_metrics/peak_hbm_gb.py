"""Layer: device.  `memory_stats()["peak_bytes_in_use"]` of the fullest
chip once the window has closed."""


def read(ctx):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
