"""Per-kernel performance attribution: device timing, XLA cost
analysis, and the roofline join.

The shipped instruments stop at the dispatch boundary: the profiler
(utils/profile.py) times spans, the ledger (utils/movement.py) prices
host<->device edges, and the sampler (utils/telemetry.py) names idle
causes — none of them can say WHICH compiled kernel inside a slow lane
burns the time, or what fraction of the chip's FLOP/byte roofline it
achieves.  Theseus (PAPERS.md) makes per-operator device-time
attribution the backbone of its optimization loop; this module is that
layer for the kernel cache.

Three pieces, riding `exec/base.py`'s `KernelCache` (every XLA dispatch
in the engine funnels through `get_or_build`):

* **Process-wide kernel catalog** — one `KernelEntry` per cached
  executable, keyed by the kernel's structural identity (cache scope +
  key, the same fingerprint the cache shares executables under).
  `_build_watched` charges builder wall time here at compile time; the
  first dispatch — the point where a lazily-jitted kernel actually
  traces and compiles — is timed separately as compile cost and
  triggers a one-shot XLA `cost_analysis()` / `memory_analysis()`
  capture (FLOPs, bytes accessed, argument/output/temp sizes).
* **Sampled timing lane** — every Nth dispatch per kernel
  (`spark.rapids.sql.profile.kernels.sampleRate`) is bracketed by
  `jax.block_until_ready` and wall-timed; the sync is accounted
  through `utils.checks.note_host_sync` (site ``kernelprof.sample``)
  so the host-sync audit — and tpulint's host-sync rule — stay honest.
  Samples land in the entry's bounded histogram and, when the calling
  thread's query is profiled, in that query's `QueryKernelLedger`
  (per-query isolation: concurrent queries sharing a cached kernel
  each see only their own dispatches).
* **Roofline join** — cost x time gives achieved GFLOP/s and GB/s per
  kernel, judged against the shared conf-overridable roofline table
  (`utils/roofline.py`, `spark.rapids.sql.profile.roofline.*`); the
  utilization reported is the max of the compute fraction and the
  HBM-bandwidth fraction, tagged with whichever resource binds.

Discipline (the profiler's): DISABLED (default) no kernel is ever
wrapped — `KernelCache` consults one module-global read and hands out
the raw executable, so the hot loop is bit-identical and
allocation-free.  Enabling is process-sticky (wrapped kernels stay in
the shared cache) but a wrapper with sampling off is a single global
read + passthrough call.
"""
from __future__ import annotations

import collections
import hashlib
import threading
import time
from typing import Optional

import jax

#: sampled-duration histogram bucket upper bounds (seconds)
TIME_BUCKETS = (1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2,
                1e-1, 3e-1, 1.0, 3.0)

#: bound on per-query Perfetto kernel samples
MAX_QUERY_SAMPLES = 1 << 12

#: bound on distinct owner describe-strings per catalog entry (shared
#: kernels accumulate owners across plan instances)
MAX_OWNERS = 8

# ---------------------------------------------------------------------------
# module state: ONE global read (`_ENABLED`) gates every hook
_ENABLED = False
_RATE = 8
_COST = True
_LOCK = threading.Lock()
#: structural identity (scope, key) -> KernelEntry
_CATALOG: "collections.OrderedDict" = collections.OrderedDict()
#: private (scope-less) KernelCache instances get a process-unique
#: token so unrelated private kernels never merge in the catalog
_PRIVATE_TOKENS = iter(range(1, 1 << 62))


def enabled() -> bool:
    """The disabled-path gate: one module-global read."""
    return _ENABLED


def maybe_enable(conf) -> bool:
    """Sticky process-wide enable, driven by the first query whose conf
    sets spark.rapids.sql.profile.kernels.enabled (the telemetry
    `maybe_start` pattern).  One global read + one conf lookup when
    off.  A later enabling conf refreshes the sample rate (last
    writer wins — the rate is process-wide, like the telemetry
    sampler's period)."""
    from spark_rapids_tpu import config as C
    if not conf[C.KERNELPROF_ENABLED]:
        return _ENABLED
    enable(conf)
    return True


def enable(conf=None) -> None:
    global _ENABLED, _RATE, _COST
    from spark_rapids_tpu import config as C
    conf = conf if conf is not None else C.get_active_conf()
    with _LOCK:
        _RATE = max(1, int(conf[C.KERNELPROF_SAMPLE_RATE]))
        _COST = bool(conf[C.KERNELPROF_COST_ANALYSIS])
        _ENABLED = True


def disable() -> None:
    """Stop sampling.  Already-wrapped kernels stay wrapped (they live
    in the shared cache) but their dispatch path degrades to one global
    read + a passthrough call."""
    global _ENABLED
    with _LOCK:
        _ENABLED = False


def reset() -> None:
    """Tests: drop the catalog and disable sampling."""
    global _ENABLED
    with _LOCK:
        _ENABLED = False
        _CATALOG.clear()


def private_token() -> int:
    return next(_PRIVATE_TOKENS)


# ---------------------------------------------------------------------------
class KernelEntry:
    """Process-lifetime attribution record for one cached executable."""

    def __init__(self, identity: tuple, cold: bool = True):
        self.identity = identity
        #: True when this entry was created at BUILD time: its first
        #: dispatch is where the lazy jit traces + compiles and must
        #: be charged as compile cost.  An entry created by the
        #: upgrade-on-cache-hit path wraps an already-WARM executable
        #: — its first dispatch is ordinary device time.
        self.cold_start = cold
        blob = repr(identity).encode()
        self.fingerprint = hashlib.md5(blob).hexdigest()[:12]
        scope, key = identity
        scope0 = scope[0] if isinstance(scope, tuple) and scope \
            and isinstance(scope[0], str) else "?"
        key0 = key[0] if isinstance(key, tuple) and key \
            and isinstance(key[0], str) else "kernel"
        #: coarse aggregation key (exec class / kernel kind) for the
        #: telemetry per-family histograms
        self.family = f"{scope0}/{key0}"
        self.label = self.family
        self._lock = threading.Lock()
        self.owners: "collections.OrderedDict[int, str]" = \
            collections.OrderedDict()
        self.members: Optional[list] = None
        self.dispatches = 0
        self.sampled = 0
        self.device_ns = 0
        #: first-dispatch wall time — where a lazily-jitted kernel
        #: actually traces + XLA-compiles
        self.compile_ns = 0
        #: builder wall time charged by KernelCache._build_watched
        self.builds = 0
        self.build_ns = 0
        #: XLA cost/memory analysis: None = not yet attempted, {} =
        #: attempted and unavailable for this executable
        self.cost: Optional[dict] = None
        self._hist = [0] * (len(TIME_BUCKETS) + 1)

    # -- recording -----------------------------------------------------------
    def note_build(self, ns: int) -> None:
        with self._lock:
            self.builds += 1
            self.build_ns += int(ns)

    def annotate(self, meta: dict) -> None:
        """Attach dispatch-site metadata (label, owning exec, fused
        member names).  Idempotent per owner; cheap enough to ride the
        per-batch get_or_build."""
        oid = meta.get("owner_id")
        with self._lock:
            if meta.get("label"):
                self.label = meta["label"]
            if meta.get("members"):
                self.members = list(meta["members"])
            if oid is not None and oid not in self.owners:
                self.owners[oid] = str(meta.get("owner", "?"))
                while len(self.owners) > MAX_OWNERS:
                    self.owners.popitem(last=False)

    def _observe(self, dt_ns: int) -> None:
        sec = dt_ns / 1e9
        idx = len(TIME_BUCKETS)
        for i, b in enumerate(TIME_BUCKETS):
            if sec <= b:
                idx = i
                break
        with self._lock:
            self.sampled += 1
            self.device_ns += dt_ns
            self._hist[idx] += 1

    # -- dispatch path -------------------------------------------------------
    def dispatch(self, fn, args, kwargs):
        with self._lock:
            self.dispatches += 1
            n = self.dispatches
        first = n == 1
        if not (first or _RATE <= 1 or n % _RATE == 0):
            out = fn(*args, **kwargs)
            self._attribute(0)
            return out
        t0 = time.perf_counter_ns()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        dt = time.perf_counter_ns() - t0
        # the timing bracket IS a blocking device sync: account it so
        # the host-sync audit (and tpulint's host-sync rule) stay clean
        from spark_rapids_tpu.utils import checks as CK
        CK.note_host_sync("kernelprof.sample")
        # a wrapper can outlive a catalog reset (it lives in the shared
        # kernel cache): re-register on sampled dispatches so the
        # catalog always reflects live kernels
        with _LOCK:
            _CATALOG.setdefault(self.identity, self)
        if first and _COST and self.cost is None:
            # one-shot cost/memory analysis (AFTER the timing bracket:
            # the AOT re-lower must not pollute the sample)
            self._capture_cost(fn, args, kwargs)
        if first and self.cold_start:
            # trace+compile happen on a cold jit's first call — charge
            # it as compile cost, never into the device-time histogram
            with self._lock:
                self.compile_ns += dt
            self._attribute(0)
        else:
            self._observe(dt)
            from spark_rapids_tpu.utils import telemetry as T
            T.note_kernel_sample(self.family, dt / 1e9)
            self._attribute(dt)
        return out

    def _attribute(self, dt_ns: int) -> None:
        """Charge this dispatch (and its sample, when timed) to the
        calling thread's query ledger, if that query is profiled with
        kernel attribution on."""
        from spark_rapids_tpu.utils import profile as P
        tr = P.tracer()
        if tr is None:
            return
        kl = getattr(tr, "kernels", None)
        if kl is not None:
            kl.note(self, dt_ns)

    def _capture_cost(self, fn, args, kwargs) -> None:
        """One-shot XLA cost/memory analysis via AOT re-lowering (the
        executable just compiled for these exact operands).  Any
        failure — non-jit callable, backend without the analysis —
        marks the entry attempted-and-empty; timing attribution keeps
        working without the roofline join."""
        cost: dict = {}
        try:
            compiled = fn.lower(*args, **kwargs).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                cost["flops"] = float(ca.get("flops", 0.0))
                cost["bytes_accessed"] = \
                    float(ca.get("bytes accessed", 0.0))
            try:
                ma = compiled.memory_analysis()
                cost["arg_bytes"] = int(ma.argument_size_in_bytes)
                cost["out_bytes"] = int(ma.output_size_in_bytes)
                cost["temp_bytes"] = int(ma.temp_size_in_bytes)
            except Exception:  # noqa: BLE001 — memory stats optional
                pass
        except Exception:  # noqa: BLE001 — analysis is best-effort
            pass
        with self._lock:
            if self.cost is None:
                self.cost = cost

    # -- views ---------------------------------------------------------------
    def mean_ns(self) -> float:
        with self._lock:
            return self.device_ns / self.sampled if self.sampled else 0.0

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "fingerprint": self.fingerprint,
                "family": self.family,
                "label": self.label,
                "owners": list(self.owners.values()),
                "members": list(self.members) if self.members else None,
                "dispatches": self.dispatches,
                "sampled": self.sampled,
                "device_ns": self.device_ns,
                "compile_ms": round(
                    (self.compile_ns + self.build_ns) / 1e6, 3),
                "builds": self.builds,
                "cost": dict(self.cost) if self.cost else None,
                "hist": list(self._hist),
            }


class WatchedKernel:
    """Transparent dispatch proxy around a cached executable: attribute
    reads fall through to the wrapped function (jit attributes like
    ``lower`` and site-attached ones like ``_ansi_labels`` keep
    working); attribute writes land on the proxy, shadowing like a
    first read would."""

    def __init__(self, entry: KernelEntry, fn):
        self._kp_entry = entry
        self._kp_fn = fn

    def __call__(self, *args, **kwargs):
        if not _ENABLED:
            return self._kp_fn(*args, **kwargs)
        return self._kp_entry.dispatch(self._kp_fn, args, kwargs)

    def __getattr__(self, name):
        return getattr(self._kp_fn, name)


# ---------------------------------------------------------------------------
# catalog access (called by exec/base.py KernelCache)
def entry_for(identity: tuple, cold: bool = True) -> KernelEntry:
    with _LOCK:
        e = _CATALOG.get(identity)
        if e is None:
            e = _CATALOG[identity] = KernelEntry(identity, cold=cold)
        return e


def watch(identity: tuple, fn, cold: bool = True) -> WatchedKernel:
    """Wrap a freshly built (`cold=True`) or cache-hit-upgraded
    (`cold=False` — the executable is already warm) callable for
    sampled attribution.  Non-callables pass through untouched."""
    if not callable(fn) or isinstance(fn, WatchedKernel):
        return fn
    return WatchedKernel(entry_for(identity, cold=cold), fn)


def annotate(fn, meta: Optional[dict]) -> None:
    """Attach dispatch-site metadata to a watched kernel AND claim it
    for the calling thread's query (the per-query owner index the
    EXPLAIN inline annotations join on)."""
    if meta is None or not isinstance(fn, WatchedKernel):
        return
    entry = fn._kp_entry
    entry.annotate(meta)
    oid = meta.get("owner_id")
    if oid is None:
        return
    from spark_rapids_tpu.utils import profile as P
    tr = P.tracer()
    if tr is None:
        return
    kl = getattr(tr, "kernels", None)
    if kl is not None:
        kl.claim(entry, oid)


def catalog() -> list:
    """Snapshot of every catalog entry (process lifetime)."""
    with _LOCK:
        entries = list(_CATALOG.values())
    return [e.snapshot() for e in entries]


def catalog_size() -> int:
    with _LOCK:
        return len(_CATALOG)


def family_device_seconds() -> dict:
    """{family: cumulative SAMPLED device seconds} across the catalog
    (the pull-side mirror of telemetry's kernel_device_seconds_total
    push counter)."""
    with _LOCK:
        entries = list(_CATALOG.values())
    out: dict = {}
    for e in entries:
        with e._lock:
            if e.device_ns:
                out[e.family] = out.get(e.family, 0.0) + e.device_ns / 1e9
    return out


# ---------------------------------------------------------------------------
class QueryKernelLedger:
    """Per-query kernel attribution (created on the QueryTracer like
    the movement ledger): which kernels THIS query dispatched, how
    often, and the device time its sampled dispatches measured —
    isolated from every concurrent query sharing the same cached
    executables."""

    def __init__(self, query_id: str, t_origin: int):
        self.query_id = query_id
        self.t_origin = t_origin
        self._lock = threading.Lock()
        #: entry -> [dispatches, sampled, device_ns]
        self._stats: "collections.OrderedDict" = collections.OrderedDict()
        #: owner exec_id -> [entry, ...] claims from this query's own
        #: get_or_build calls (never another query's)
        self._owners: dict = {}
        #: (ts_ns, dur_ns, fingerprint, label, tid) Perfetto samples
        self._samples: "collections.deque" = \
            collections.deque(maxlen=MAX_QUERY_SAMPLES)

    def note(self, entry: KernelEntry, dt_ns: int) -> None:
        ts = time.perf_counter_ns() - self.t_origin
        with self._lock:
            st = self._stats.get(entry)
            if st is None:
                st = self._stats[entry] = [0, 0, 0]
            st[0] += 1
            if dt_ns:
                st[1] += 1
                st[2] += dt_ns
                self._samples.append(
                    (ts - dt_ns, dt_ns, entry.fingerprint, entry.label,
                     threading.current_thread().ident or 0))

    def claim(self, entry: KernelEntry, owner_id: int) -> None:
        with self._lock:
            lst = self._owners.setdefault(owner_id, [])
            if entry not in lst:
                lst.append(entry)

    def samples(self) -> list:
        with self._lock:
            return list(self._samples)

    # -- the report ----------------------------------------------------------
    def report(self, conf=None) -> list:
        """One row per kernel this query dispatched, hottest first:
        dispatch counts, estimated cumulative device time (sampled
        mean x dispatches; the process-wide mean backstops kernels
        this query never sampled), compile ms, XLA cost, achieved
        GFLOP/s / GB/s, and the roofline fraction with whichever
        resource binds."""
        from spark_rapids_tpu.utils import roofline as RL
        peak_gf = RL.peak_gflops(conf)
        hbm = RL.hbm_gbps(conf)
        with self._lock:
            items = [(e, list(st)) for e, st in self._stats.items()]
            owners = {oid: list(es) for oid, es in self._owners.items()}
        entry_owner: dict = {}
        for oid, es in owners.items():
            for e in es:
                entry_owner.setdefault(e, oid)
        rows = []
        for e, (disp, sampled, ns) in items:
            mean = (ns / sampled) if sampled else e.mean_ns()
            est_ns = mean * disp
            snap = e.snapshot()
            row = {
                "fingerprint": e.fingerprint,
                "family": e.family,
                "label": e.label,
                "owner_id": entry_owner.get(e),
                "owners": snap["owners"],
                "members": snap["members"],
                "dispatches": disp,
                "sampled": sampled,
                "device_ms": round(est_ns / 1e6, 3),
                "avg_ms": round(mean / 1e6, 4),
                "compile_ms": snap["compile_ms"],
            }
            cost = snap["cost"]
            if cost and est_ns > 0:
                est_s = est_ns / 1e9
                flops = cost.get("flops", 0.0) * disp
                byts = cost.get("bytes_accessed", 0.0) * disp
                row["flops_per_dispatch"] = cost.get("flops", 0.0)
                row["bytes_per_dispatch"] = cost.get("bytes_accessed",
                                                     0.0)
                row["temp_bytes"] = cost.get("temp_bytes", 0)
                gf = flops / est_s / 1e9
                gb = byts / est_s / 1e9
                row["gflops"] = round(gf, 3)
                row["gbps"] = round(gb, 3)
                if peak_gf is None or hbm is None:
                    # a device with no nominal peaks (utils/roofline
                    # DEVICE_PEAKS): no share, never another chip's
                    row["roofline_pct"] = row["bound"] = None
                else:
                    cf, mf = gf / peak_gf, gb / hbm
                    row["roofline_pct"] = round(100.0 * max(cf, mf), 3)
                    row["bound"] = "compute" if cf >= mf else "memory"
            rows.append(row)
        rows.sort(key=lambda r: r["device_ms"], reverse=True)
        return rows


def format_report(rows: list, top_n: int = 12) -> str:
    """Human rendering for the QueryProfile's '-- kernels --' section."""
    if not rows:
        return "<no kernel dispatches attributed>"
    total_ms = sum(r["device_ms"] for r in rows)
    lines = [f"attributed device time: {total_ms:.1f} ms over "
             f"{sum(r['dispatches'] for r in rows)} dispatches "
             f"({len(rows)} kernels, top {min(top_n, len(rows))})"]
    for r in rows[:top_n]:
        roof = (f"  {r['gflops']:.1f} GF/s {r['gbps']:.2f} GB/s "
                f"{r['roofline_pct']:.2f}% roofline ({r['bound']})"
                if r.get("roofline_pct") is not None else "")
        owner = f"  <- {r['owners'][0]}" if r["owners"] else ""
        members = (f" [{'+'.join(r['members'])}]"
                   if r["members"] else "")
        lines.append(
            f"  {r['device_ms']:9.1f} ms  x{r['dispatches']:<5d} "
            f"(avg {r['avg_ms']:.2f} ms, compile "
            f"{r['compile_ms']:.0f} ms)  {r['fingerprint']} "
            f"{r['label']}{members}{roof}{owner}")
    return "\n".join(lines)
