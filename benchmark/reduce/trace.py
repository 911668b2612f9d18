"""From a profiler trace (`.xplane.pb`) to numbers: device busy time,
idle gaps and what the host was doing in them, device operations by
total time.  Read with `jax.profiler.ProfileData`, in-process.

A device plane is named `/device:TPU:<n>`; its line `XLA Ops` holds
one event per operation the chip's core ran, with start and duration
in nanoseconds on the profiler's clock, named by its HLO text; `XLA
Modules` holds one event per executable (looked at by hand in a trace
of TPC-H q6 on a v5e, PR 26; `Async XLA Ops`, the DMA copies that
overlap the core, are not counted as busy).  Host threads are lines of the
plane `/host:CPU`; a `jax.profiler.TraceAnnotation` is an event there,
on the same clock.  Busy time of a chip is the union of its operations'
intervals inside the window.
"""
from __future__ import annotations

import glob
import gzip
import os
import re

DEVICE_PLANE = "/device:TPU:"
DEVICE_OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: host spans that explain a gap, by prefix: the program's own
#: (`exec:<operator>` from utils/profile) and the benchmark's
SPAN_PREFIXES = ("exec:", "bench:")


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


_LAYOUT = re.compile(r"\{[^}]*\}")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi) given merged busy intervals."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def short_op(name: str, module: str = "") -> str:
    """A device operation's HLO text cut to what tells it apart:
    `<module>/<result name> <result type> <opcode>[ <fusion kind>]`.
    The package names no kernel (`jax.named_scope`), so the module is
    jit's own name with its fingerprint dropped."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return (f"{module}/{name}" if module else name)[:96]
    rhs = _LAYOUT.sub("", rhs)
    op = _OPCODE.search(rhs)
    rtype = (rhs[:op.start()] if op else rhs).strip()[:40]
    text = f"{lhs} {rtype} {op.group(1) if op else ''}".rstrip()
    if "kind=" in rhs:
        text += " " + rhs.split("kind=", 1)[1].split(",", 1)[0].strip()
    return (f"{module}/{text}" if module else text)[:96]


def _module_of(modules: list, starts: list, at: float) -> str:
    import bisect
    i = bisect.bisect_right(starts, at) - 1
    if i >= 0 and modules[i][2] >= at:
        return modules[i][0].split("(", 1)[0]
    return ""


def read_planes(path: str, device_plane: str = DEVICE_PLANE,
                op_lines=DEVICE_OP_LINES) -> dict:
    """{"devices": {plane: [(name, start_ns, end_ns)]},
        "spans": [(name, start_ns, end_ns)]} from one trace file."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_plane):
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                 for ev in (lines[MODULE_LINE].events
                            if MODULE_LINE in lines else ())),
                key=lambda m: m[1])
            starts = [m[1] for m in modules]
            ops = []
            for name in op_lines:
                if name in lines:
                    ops += [(short_op(ev.name, _module_of(
                                modules, starts, ev.start_ns)),
                             ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in lines[name].events]
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns,
                           ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIXES)]
    return {"devices": devices, "spans": spans}


def _span_in(spans: list, lo: float, hi: float) -> str:
    """The innermost host span that covers most of [lo, hi): of the
    spans open over at least half of the gap, the shortest."""
    best, best_len = "(no span)", None
    for name, s, e in spans:
        if min(e, hi) - max(s, lo) >= 0.5 * (hi - lo):
            if best_len is None or e - s < best_len:
                best, best_len = name, e - s
    return best


def reduce_planes(planes: dict, window=None, top: int = 10) -> dict:
    """The numbers of one traced window.  `window` is (start_ns, end_ns)
    on the trace's clock; without it, the window spans the first
    `bench:` span's start to the last one's end (or, with none, the
    first device operation to the last)."""
    devices, spans = planes["devices"], planes["spans"]
    if window is None:
        marks = [(s, e) for n, s, e in spans if n.startswith("bench:")] \
            or [(s, e) for ops in devices.values() for _, s, e in ops]
        if not marks:
            return {}
        window = (min(s for s, _ in marks), max(e for _, e in marks))
    lo, hi = window
    per_chip, by_op = {}, {}
    for plane, ops in devices.items():
        inside = clip([[s, e] for _, s, e in ops], lo, hi)
        per_chip[plane] = union(inside)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_op[name] = by_op.get(name, 0.0) + d
    busy = {p: sum(e - s for s, e in iv) for p, iv in per_chip.items()}
    if not busy or max(busy.values()) <= 0:
        return {}
    busiest = max(busy, key=busy.get)
    by_span = {}
    for s, e in gaps(per_chip[busiest], lo, hi):
        name = _span_in(spans, s, e)
        by_span[name] = by_span.get(name, 0.0) + (e - s)
    rank = lambda d: [[k, v / 1e9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s_mean": sum(busy.values()) / len(busy) / 1e9,
        "busy_s_busiest": busy[busiest] / 1e9,
        "busy_s_by_chip": {p: v / 1e9 for p, v in sorted(busy.items())},
        "chips": len(busy),
        "device_ops": rank(by_op),
        "idle_gaps": rank(by_span),
    }
