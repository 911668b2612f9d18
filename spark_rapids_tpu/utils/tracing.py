"""Trace annotation (reference NVTX ranges, `NvtxWithMetrics.scala:27`).

On TPU the profiler story is xprof/Perfetto: `jax.profiler.TraceAnnotation`
marks host-side ranges that show up in `jax.profiler.trace` captures.  The
per-query span tracer (utils/profile.py) dual-emits every span through
`annotation`, so its spans sit on the device trace's clock."""
from __future__ import annotations

from contextlib import nullcontext

import jax


def annotation(name: str):
    """A `jax.profiler.TraceAnnotation` context for `name`, degrading
    to a null context when the profiler cannot construct one (e.g. a
    backend without host tracing) — never raising into the caller."""
    try:
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return nullcontext()
