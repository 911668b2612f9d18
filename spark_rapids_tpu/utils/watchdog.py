"""Query watchdog: hang detection, deadlines, cooperative cancellation.

The reference plugin inherits Spark's task-level liveness machinery
(speculation, task kill, executor heartbeats); this standalone engine
has none, so a dead collective participant, a stalled shuffle handler,
a wedged pyudf worker, or a pathological XLA compile would hang a query
forever — the one failure mode the OOM retry harness (memory/retry.py)
and shuffle fault recovery (shuffle/recovery.py) cannot reach, because
both only trigger on *raised* errors.  Distributed engines (Theseus,
PAPERS.md) treat bounded-time data movement as a first-class invariant
for the same reason; on a TPU pod it is worse, since ICI collectives
block every participant when one goes dark.

Three pieces:

* **Heartbeat** — every long-lived activity (prefetch producer loops,
  shuffle server handlers and client fetch loops, collective-exchange
  dispatches, AQE stage fills, pyudf workers, KernelCache compiles)
  registers a handle with a progress counter and a deadline class
  (`spark.rapids.sql.watchdog.taskTimeout` / `.collectiveTimeout` /
  `.compileTimeout`).  `beat()` on every unit of progress; `pause()`
  around waits attributable to a *different* watched party (a producer
  parked on a full queue is the consumer's problem, not a hang; a
  task whose thread sits in the XLA compiler is the compile
  heartbeat's: `compiling()`).
* **Scanner** — a daemon thread polls registered heartbeats every
  `watchdog.pollInterval` seconds.  No progress past the deadline
  emits ONE diagnostic dump (all thread stacks, TpuSemaphore holders,
  prefetch queue stats, in-flight shuffle fetches, hang-injection
  state) and fires the query's CancelToken.
* **CancelToken** — per-query cooperative cancellation, installed by
  the outermost `TpuExec.collect` and threaded through TaskContext to
  producer threads.  Every indefinite wait in the engine is a bounded
  poll + token check (`check_cancelled`), so a cancelled query
  terminates with a descriptive `TpuQueryTimeout` carrying the dump,
  releases its resources (semaphore permits, producer threads, open
  fetches), and leaves the process healthy for the next query.

A seeded hang injector (`spark.rapids.memory.faultInjection.hangSite`
/ `.hangAfterBatches`) blocks the named site until the token fires —
cancellation is cooperative, exactly like a Spark task kill — so the
whole detect -> dump -> cancel -> release lattice is exercised on CPU
CI without a real dead peer.
"""
from __future__ import annotations

import logging
import sys
import threading
import time
import traceback
from contextlib import ExitStack, contextmanager
from typing import Callable, Optional

from spark_rapids_tpu import config as C

log = logging.getLogger("spark_rapids_tpu.watchdog")

#: deadline class -> conf entry
_DEADLINE_ENTRIES = {
    "task": C.WATCHDOG_TASK_TIMEOUT,
    "collective": C.WATCHDOG_COLLECTIVE_TIMEOUT,
    "compile": C.WATCHDOG_COMPILE_TIMEOUT,
}

#: harness-level defaults (tests/conftest.py installs conservative
#: suite-wide deadlines here); an EXPLICIT session-conf setting wins
_GLOBAL_DEFAULTS: dict = {}

#: granularity of cancellable waits; latency only paid on cancel edges
_POLL_S = 0.05

#: hard cap on an injected hang with no watchdog to cancel it — a
#: misconfigured test must fail loudly, never eat the CI wall clock
_HANG_HARD_CAP_S = 120.0


class TpuQueryTimeout(RuntimeError):
    """The watchdog declared the query hung and cancelled it.  Carries
    the diagnostic dump taken at detection time (`.dump`)."""

    def __init__(self, message: str, dump: Optional[str] = None):
        self.dump = dump
        super().__init__(message if not dump
                         else f"{message}\n{dump}")


class CancelToken:
    """Per-query cooperative cancellation.  `cancel()` is one-shot;
    every bounded poll in the engine calls `check()` which raises
    `TpuQueryTimeout` once the token has fired."""

    def __init__(self):
        self._ev = threading.Event()
        self._lock = threading.Lock()
        self.reason: Optional[str] = None
        self.dump: Optional[str] = None

    @property
    def cancelled(self) -> bool:
        return self._ev.is_set()

    def cancel(self, reason: str, dump: Optional[str] = None) -> None:
        with self._lock:
            if self._ev.is_set():
                return
            self.reason = reason
            self.dump = dump
            self._ev.set()
        from spark_rapids_tpu.utils import profile as P
        P.event(P.EV_CANCEL, reason=reason)

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._ev.wait(timeout)

    def check(self) -> None:
        if self._ev.is_set():
            raise TpuQueryTimeout(
                f"query cancelled by watchdog: {self.reason}",
                dump=self.dump)


class AttemptToken(CancelToken):
    """Per-attempt cancellation for racing duplicate work (speculative
    task attempts, hedged fetches): linked to a parent (the query's
    token), so a check honors BOTH — the query dying cancels every
    attempt, while cancelling one losing attempt leaves the query and
    its sibling attempt untouched.  `race_lost` marks a cancellation
    that means "a faster attempt won", letting the attempt runner
    swallow it instead of failing the query."""

    def __init__(self, parent: Optional[CancelToken] = None):
        super().__init__()
        self.parent = parent
        self.race_lost = False

    @property
    def cancelled(self) -> bool:
        return self._ev.is_set() or (
            self.parent is not None and self.parent.cancelled)

    def cancel_race_lost(self, reason: str) -> None:
        """Cancel because the sibling attempt finished first.  One-shot
        like cancel(); the flag is set before the event so a woken
        waiter always sees it."""
        self.race_lost = True
        self.cancel(reason)

    def check(self) -> None:
        if self.parent is not None:
            self.parent.check()
        if self._ev.is_set():
            raise TpuQueryTimeout(
                f"attempt cancelled: {self.reason}", dump=self.dump)

    def wait(self, timeout: Optional[float] = None) -> bool:
        if self.parent is None:
            return self._ev.wait(timeout)
        # poll both events in bounded slices so a parent cancellation
        # wakes an attempt parked on its own (unfired) token
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        while True:
            if self._ev.is_set() or self.parent.cancelled:
                return True
            if deadline is None:
                slice_s = _POLL_S
            else:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                slice_s = min(left, _POLL_S)
            self._ev.wait(slice_s)


#: thread-local attempt-token stack: an attempt runner installs its
#: AttemptToken here so every cancellation point under it (batch
#: boundaries, backoff sleeps, injected delays) honors the attempt's
#: cancellation, not just the query's
_ATTEMPT_TLS = threading.local()


@contextmanager
def attempt_scope(token: CancelToken):
    """Install `token` as this thread's innermost cancellation token
    for the duration (speculative/hedged attempt bodies)."""
    prev = getattr(_ATTEMPT_TLS, "tok", None)
    _ATTEMPT_TLS.tok = token
    try:
        yield token
    finally:
        _ATTEMPT_TLS.tok = prev


# ---------------------------------------------------------------------------
# token management: every query owns its token on its QueryContext
# (exec/scheduler.py), installed thread-locally by the outermost
# collect and threaded to helper threads via `TaskContext.query_ctx` /
# `ctx.cancel_token` — so cancelling query A can never reach a thread
# working for query B.  The process-global token remains only as the
# fallback for threads with no query identity at all (shuffle server
# accept loops, bare tests).
_TOKEN_LOCK = threading.Lock()
_TOKEN = CancelToken()


def _current_query_ctx():
    try:
        from spark_rapids_tpu.exec import scheduler as S
        return S.current()
    except ImportError:
        return None


def current_token() -> CancelToken:
    tok = getattr(_ATTEMPT_TLS, "tok", None)
    if tok is not None:
        return tok
    from spark_rapids_tpu.memory.semaphore import TaskContext
    ctx = TaskContext.get()
    tok = getattr(ctx, "cancel_token", None) if ctx is not None else None
    if tok is not None:
        return tok
    qc = _current_query_ctx()
    if qc is not None:
        return qc.token
    with _TOKEN_LOCK:
        return _TOKEN


def begin_query() -> CancelToken:
    """Reset the process-global FALLBACK token + stats (hygiene for
    query-less legacy paths and tests; queries proper each carry their
    own token on their QueryContext).  Returns the fresh token."""
    global _TOKEN
    with _TOKEN_LOCK:
        _TOKEN = CancelToken()
        tok = _TOKEN
    with _STATS_LOCK:
        for k in _QUERY_STATS:
            _QUERY_STATS[k] = 0
    return tok


def check_cancelled() -> None:
    """Raise TpuQueryTimeout if the current query has been cancelled.
    One Event check — cheap enough for batch boundaries and poll
    loops."""
    current_token().check()


def cancellable_sleep(seconds: float) -> None:
    """Bounded-poll sleep that raises TpuQueryTimeout the moment the
    query's token fires (backoff sleeps must not outlive the query)."""
    tok = current_token()
    deadline = time.monotonic() + seconds
    while True:
        tok.check()
        left = deadline - time.monotonic()
        if left <= 0:
            return
        if tok.wait(min(left, _POLL_S)):
            tok.check()


def cancellable_wait(ev: threading.Event, timeout: float) -> bool:
    """Wait on `ev` up to `timeout` seconds in bounded slices, raising
    TpuQueryTimeout if the query is cancelled meanwhile.  Returns
    whether the event was set (False = timed out)."""
    deadline = time.monotonic() + timeout
    while True:
        check_cancelled()
        left = deadline - time.monotonic()
        if left <= 0:
            return ev.is_set()
        if ev.wait(min(left, max(_POLL_S, timeout / 100.0))):
            return True


# ---------------------------------------------------------------------------
# per-query + process-lifetime stats
_STATS_LOCK = threading.Lock()
_QUERY_STATS = {"timeouts": 0, "cancels": 0, "dumps": 0,
                "slowest_heartbeat_ms": 0}
_TOTAL_STATS = {"timeouts": 0, "cancels": 0, "dumps": 0}


def query_stats() -> dict:
    """Watchdog counters for the CURRENT query (its QueryContext's
    stats — the per-query view `TpuExec.collect` charges to the plan's
    metrics); the process-global legacy stats when no query context is
    installed."""
    qc = _current_query_ctx()
    if qc is not None:
        with _STATS_LOCK:
            return dict(qc.stats)
    with _STATS_LOCK:
        return dict(_QUERY_STATS)


def watchdog_stats() -> dict:
    """Process-lifetime counters (CI summary lines)."""
    with _STATS_LOCK:
        return dict(_TOTAL_STATS)


def _note_gap(ms: float, qc=None) -> None:
    """Charge a heartbeat gap to its OWN query's stats (`qc` captured
    at heartbeat creation), falling back to the legacy global."""
    stats = qc.stats if qc is not None else _QUERY_STATS
    with _STATS_LOCK:
        if ms > stats["slowest_heartbeat_ms"]:
            stats["slowest_heartbeat_ms"] = int(ms)


def _note_fire(dumped: bool, qc=None) -> None:
    per_query = qc.stats if qc is not None else _QUERY_STATS
    with _STATS_LOCK:
        for s in (per_query, _TOTAL_STATS):
            s["timeouts"] += 1
            s["cancels"] += 1
            if dumped:
                s["dumps"] += 1


# ---------------------------------------------------------------------------
def deadline_for(kind: str, conf: Optional[C.RapidsConf] = None) -> float:
    """Resolve a deadline class to seconds: an explicit session-conf
    setting wins, then the harness global default (configure_global),
    then the registry default."""
    entry = _DEADLINE_ENTRIES[kind]
    conf = conf if conf is not None else C.get_active_conf()
    if conf.is_set(entry.key):
        return float(conf[entry])
    if kind in _GLOBAL_DEFAULTS:
        return float(_GLOBAL_DEFAULTS[kind])
    return float(conf[entry])


def configure_global(task_timeout: Optional[float] = None,
                     collective_timeout: Optional[float] = None,
                     compile_timeout: Optional[float] = None,
                     poll_interval: Optional[float] = None) -> None:
    """Install harness-level default deadlines (tests/conftest.py uses
    this to arm a conservative suite-wide watchdog so a genuine hang in
    tier-1 fails fast with a dump instead of burning the wall-clock
    budget).  Explicit per-session conf settings still win."""
    for k, v in (("task", task_timeout),
                 ("collective", collective_timeout),
                 ("compile", compile_timeout),
                 ("poll", poll_interval)):
        if v is None:
            _GLOBAL_DEFAULTS.pop(k, None)
        else:
            _GLOBAL_DEFAULTS[k] = float(v)


def _poll_for(conf: Optional[C.RapidsConf] = None) -> float:
    conf = conf if conf is not None else C.get_active_conf()
    if conf.is_set(C.WATCHDOG_POLL_INTERVAL.key):
        return float(conf[C.WATCHDOG_POLL_INTERVAL])
    if "poll" in _GLOBAL_DEFAULTS:
        return float(_GLOBAL_DEFAULTS["poll"])
    return float(conf[C.WATCHDOG_POLL_INTERVAL])


# ---------------------------------------------------------------------------
_HB_LOCK = threading.Lock()
_HEARTBEATS: dict[int, "Heartbeat"] = {}
_HB_IDS = iter(range(1, 1 << 62))


class Heartbeat:
    """One watched activity.  `beat()` on every unit of progress;
    `pause()` around waits attributable to another watched party
    (backpressure parking is not a hang).  Context manager:
    registration on entry, removal on exit."""

    def __init__(self, name: str, kind: str, deadline: float,
                 poll: float, token: CancelToken, dump: bool,
                 details: Optional[Callable[[], str]] = None,
                 slow_check: Optional[Callable[["Heartbeat", float],
                                               None]] = None):
        self.name = name
        self.kind = kind
        self.deadline = deadline
        self.poll = poll
        self.token = token
        self.dump_on_timeout = dump
        self.details = details
        #: optional *slow* classifier (distinct from hung): the scanner
        #: calls it every scan with (heartbeat, now) while the activity
        #: is live — the speculation layer uses it to compare a task's
        #: elapsed runtime against its stage's completed-task median
        #: and launch a duplicate attempt.  A beating heartbeat can
        #: still be slow; only a silent one is hung.
        self.slow_check = slow_check
        self.thread_name = threading.current_thread().name
        self.thread_id = threading.get_ident()
        self.created = time.monotonic()
        self.last_beat = self.created
        self.beats = 0
        self.fired = False
        self._paused = 0
        self._id = next(_HB_IDS)
        #: the owning query (None outside a query): gap stats charge
        #: HERE and a timeout fires THIS query's token/event log only
        self.qc = _current_query_ctx()

    def beat(self, n: int = 1) -> None:
        now = time.monotonic()
        _note_gap((now - self.last_beat) * 1000.0, self.qc)
        self.last_beat = now
        self.beats += n

    @contextmanager
    def pause(self):
        self._paused += 1
        try:
            yield
        finally:
            self._paused -= 1
            # the wait we sat out is not this activity's staleness
            self.last_beat = time.monotonic()

    def close(self) -> None:
        with _HB_LOCK:
            _HEARTBEATS.pop(self._id, None)

    def __enter__(self) -> "Heartbeat":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def describe(self) -> str:
        age = time.monotonic() - self.last_beat
        q = f" query={self.qc.query_id}" if self.qc is not None else ""
        return (f"{self.name} [{self.kind}]{q} beats={self.beats} "
                f"last_progress={age:.1f}s ago deadline="
                f"{self.deadline:.1f}s thread={self.thread_name}")


class _NullHeartbeat(Heartbeat):
    """Watchdog disabled: same surface, no registration, no scanning."""

    def __init__(self):
        pass

    def beat(self, n: int = 1) -> None:
        pass

    @contextmanager
    def pause(self):
        yield

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


_NULL_HB = _NullHeartbeat()


def enabled(conf: Optional[C.RapidsConf] = None) -> bool:
    conf = conf if conf is not None else C.get_active_conf()
    return bool(conf[C.WATCHDOG_ENABLED])


def heartbeat(name: str, kind: str = "task",
              details: Optional[Callable[[], str]] = None,
              conf: Optional[C.RapidsConf] = None,
              slow_check: Optional[Callable] = None) -> Heartbeat:
    """Register a watched activity under the current query's token.
    Returns a no-op handle when the watchdog is disabled, so call
    sites need no conditional."""
    conf = conf if conf is not None else C.get_active_conf()
    if not enabled(conf):
        return _NULL_HB
    hb = Heartbeat(name, kind, deadline_for(kind, conf),
                   _poll_for(conf), current_token(),
                   bool(conf[C.WATCHDOG_DUMP_ON_TIMEOUT]), details,
                   slow_check=slow_check)
    with _HB_LOCK:
        _HEARTBEATS[hb._id] = hb
    _ensure_scanner()
    # wake a mid-sleep scanner so a freshly registered short-deadline
    # heartbeat is picked up at ITS poll cadence, not the previous one
    _SCAN_WAKE.set()
    return hb


def active_heartbeats() -> list[Heartbeat]:
    with _HB_LOCK:
        return list(_HEARTBEATS.values())


@contextmanager
def compiling(label: str, conf: Optional[C.RapidsConf] = None):
    """An XLA compile on this thread: watched by a compile-class
    heartbeat of its own, while every heartbeat this thread registered
    before it pauses.  The compiler's minutes are the compiler's
    staleness, not the enclosing task's: a producer task that compiles
    three kernels back to back on an empty cache made no progress the
    task deadline should count (a cold TPC-H q1 on the chip: 74 + 203 +
    12 s inside one exchange-map task against a 300 s deadline)."""
    me = threading.get_ident()
    mine = [hb for hb in active_heartbeats() if hb.thread_id == me]
    with ExitStack() as held:
        for hb in mine:
            held.enter_context(hb.pause())
        with heartbeat(label, kind="compile", conf=conf):
            yield


# ---------------------------------------------------------------------------
_SCANNER_LOCK = threading.Lock()
_SCANNER: Optional[threading.Thread] = None
_SCAN_WAKE = threading.Event()


def _ensure_scanner() -> None:
    global _SCANNER
    with _SCANNER_LOCK:
        if _SCANNER is not None and _SCANNER.is_alive():
            return
        _SCANNER = threading.Thread(target=_scan_loop, daemon=True,
                                    name="tpu-watchdog")
        _SCANNER.start()


def _scan_loop() -> None:
    while True:
        hbs = active_heartbeats()
        sleep_s = min([hb.poll for hb in hbs] or [1.0])
        if _SCAN_WAKE.wait(max(0.01, min(sleep_s, 5.0))):
            _SCAN_WAKE.clear()
        now = time.monotonic()
        for hb in active_heartbeats():
            if hb._paused > 0 or hb.fired or hb.token.cancelled:
                # one dump per cancellation: sibling activities all
                # stall once their query is cancelled — re-dumping
                # each would bury the first (causal) dump
                continue
            if hb.slow_check is not None:
                # slow classification rides the same scan: a beating
                # but lagging activity is *slow*, never *hung* — the
                # callback decides (and launches speculation) without
                # touching the hang deadline below
                try:
                    hb.slow_check(hb, now)
                except Exception:  # noqa: BLE001 — a classifier bug
                    log.exception("slow_check failed for %s", hb.name)
            gap = now - hb.last_beat
            _note_gap(gap * 1000.0, hb.qc)
            if gap > hb.deadline:
                hb.fired = True
                _fire(hb, gap)


def _fire(hb: Heartbeat, gap: float) -> None:
    reason = (f"no progress from {hb.name} for {gap:.1f}s "
              f"(watchdog {hb.kind} deadline "
              f"{hb.deadline:.1f}s, "
              f"{_DEADLINE_ENTRIES[hb.kind].key})")
    dump = None
    if hb.dump_on_timeout:
        try:
            dump = build_dump(stuck=hb)
        except Exception as e:  # noqa: BLE001 — the dump must never
            dump = f"<diagnostic dump failed: {e}>"  # mask the timeout
    _note_fire(dump is not None, hb.qc)
    # one CORRELATED record (query id + site + full dump) in the
    # structured event log, attributed to the STUCK query's own event
    # log (the scanner thread itself belongs to no query); the token
    # cancel event inside cancel() rides the same scope.  dumpOnTimeout
    # keeps the console copy below.
    from spark_rapids_tpu.exec import scheduler as S
    from spark_rapids_tpu.utils import profile as P
    with S.scoped(hb.qc):
        P.event(P.EV_WATCHDOG_TIMEOUT, heartbeat=hb.name,
                deadline_class=hb.kind, gap_s=round(gap, 2),
                deadline_s=hb.deadline, stuck_thread=hb.thread_name,
                reason=reason, dump=dump)
        log.error("watchdog timeout: %s%s", reason,
                  "\n" + dump if dump else "")
        hb.token.cancel(reason, dump)


# ---------------------------------------------------------------------------
def build_dump(stuck: Optional[Heartbeat] = None) -> str:
    """One diagnostic snapshot: the stuck activity, every registered
    heartbeat, all thread stacks, TpuSemaphore holders, prefetch
    pipeline stats, in-flight shuffle fetches, and hang-injection
    state.  Every section is individually guarded — a dump must never
    fail."""
    lines = ["==== TPU query watchdog dump ===="]
    if stuck is not None:
        lines.append(f"stuck: {stuck.describe()}")
        if stuck.details is not None:
            try:
                lines.append(f"stuck details: {stuck.details()}")
            except Exception as e:  # noqa: BLE001
                lines.append(f"stuck details: <failed: {e}>")
    lines.append("-- heartbeats --")
    for hb in active_heartbeats():
        mark = " (PAUSED)" if hb._paused > 0 else ""
        lines.append(f"  {hb.describe()}{mark}")
    lines.append("-- semaphore --")
    try:
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        sem = TpuSemaphore.get()
        snap = sem.snapshot()
        lines.append(f"  holders={len(snap['refs'])} "
                     f"max_concurrent={sem.max_concurrent} "
                     f"refs={snap['refs']} "
                     f"query_holds={snap['queryHolds']} "
                     f"longest_wait_ms={snap['longestWaitMs']}")
        for w in snap["waiters"]:
            lines.append(f"  waiting: {w}")
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("-- query scheduler --")
    try:
        from spark_rapids_tpu.exec.scheduler import QueryScheduler
        lines.append(f"  {QueryScheduler.get().describe()}")
        lines.append(f"  stats={QueryScheduler.get().stats()}")
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("-- prefetch pipeline --")
    try:
        from spark_rapids_tpu.exec.pipeline import pipeline_stats
        lines.append(f"  {pipeline_stats()}")
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("-- in-flight shuffle fetches --")
    try:
        from spark_rapids_tpu.shuffle.client_server import inflight_fetches
        flights = inflight_fetches()
        if not flights:
            lines.append("  (none)")
        for f in flights:
            lines.append(f"  {f}")
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("-- speculation / slow injection --")
    try:
        from spark_rapids_tpu.exec.speculation import speculation_stats
        lines.append(f"  {speculation_stats()} "
                     f"slow_injected={slow_injection_counts()}")
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("-- residency --")
    try:
        # the HBM holder table (utils/residency.py): an OOM-adjacent
        # post-mortem shows WHO owned the memory, not just how much
        # was resident
        from spark_rapids_tpu.utils import residency as RS
        lines.append(RS.describe_for_dump())
        from spark_rapids_tpu.memory.device_manager import DeviceManager
        dm = DeviceManager.peek()
        if dm is not None:
            lines.append(f"  accounting: {dm.snapshot()}")
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("-- telemetry --")
    try:
        # engine-wide state (gauges + recent utilization samples) so a
        # post-mortem shows what the whole process was doing, not just
        # the stuck query's threads
        from spark_rapids_tpu.utils import telemetry as T
        lines.append(T.describe_for_dump())
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("-- hang injection --")
    try:
        with _INJ_LOCK:
            lines.append(f"  counters={dict(_INJ_COUNTS)} "
                         f"hanging={sorted(_INJ_HANGING)}")
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("-- thread stacks --")
    try:
        names = {t.ident: t.name for t in threading.enumerate()}
        for tid, frame in sys._current_frames().items():
            lines.append(f"  thread {names.get(tid, '?')} ({tid}):")
            for fl in traceback.format_stack(frame):
                lines.extend("    " + ln
                             for ln in fl.rstrip().splitlines())
    except Exception as e:  # noqa: BLE001
        lines.append(f"  <unavailable: {e}>")
    lines.append("==== end watchdog dump ====")
    return "\n".join(lines)


def thread_stack(thread_id: Optional[int]) -> str:
    """Formatted stack of one thread (leak diagnostics); empty string
    when the thread is gone or frames are unavailable."""
    try:
        frame = sys._current_frames().get(thread_id)
        if frame is None:
            return ""
        return "".join(traceback.format_stack(frame))
    except Exception:  # noqa: BLE001
        return ""


# ---------------------------------------------------------------------------
# seeded hang injection
_INJ_LOCK = threading.Lock()
_INJ_COUNTS: dict[str, int] = {}
_INJ_HANGING: set[str] = set()

HANG_SITES = ("producer", "collective", "shuffle-server", "pyudf",
              "compile")


def reset_hang_injection() -> None:
    with _INJ_LOCK:
        _INJ_COUNTS.clear()
        _INJ_HANGING.clear()


def maybe_hang(site: str, conf: Optional[C.RapidsConf] = None) -> None:
    """Hang-injection hook, called once per unit of progress at each
    instrumented site.  When `faultInjection.hangSite` names this site
    and its progress budget (`hangAfterBatches`) is exhausted, block —
    the site's heartbeat stops beating, the watchdog detects the
    stall, dumps, and fires the CancelToken, at which point this
    raises TpuQueryTimeout (cooperative cancellation, like a Spark
    task kill reaching a blocked task)."""
    conf = conf if conf is not None else C.get_active_conf()
    target = str(conf[C.HANG_INJECT_SITE])
    if not target or target != site:
        return
    after = int(conf[C.HANG_INJECT_AFTER])
    with _INJ_LOCK:
        n = _INJ_COUNTS.get(site, 0) + 1
        _INJ_COUNTS[site] = n
        if n <= after:
            return
        _INJ_HANGING.add(site)
    tok = current_token()
    log.warning("hang injection engaged at site '%s' (progress %d > "
                "hangAfterBatches=%d); blocking until the watchdog "
                "cancels the query", site, n, after)
    t0 = time.monotonic()
    try:
        while not tok.wait(_POLL_S):
            if time.monotonic() - t0 > _HANG_HARD_CAP_S:
                raise RuntimeError(
                    f"injected hang at '{site}' exceeded the "
                    f"{_HANG_HARD_CAP_S:.0f}s hard cap without a "
                    "watchdog cancel — is watchdog.enabled off while "
                    "hang injection is on?")
    finally:
        with _INJ_LOCK:
            _INJ_HANGING.discard(site)
    raise TpuQueryTimeout(
        f"hang-injected site '{site}' cancelled: {tok.reason}",
        dump=tok.dump)


# ---------------------------------------------------------------------------
# seeded slow (straggler) injection — the *slow* sibling of maybe_hang:
# the site stays alive and keeps beating, just 10x (slowFactor) slower,
# so the tail-tolerance layer (speculation, hedged fetches) is what has
# to save the query, not the hang watchdog
SLOW_SITES = ("map-task", "shuffle-server")

#: per-unit delay hard cap — a misconfigured factor must never turn a
#: soak test into a wall-clock sink
_SLOW_HARD_CAP_S = 2.0

_SLOW_LOCK = threading.Lock()
_SLOW_COUNTS: dict[str, int] = {}
_SLOW_RNGS: dict = {}


def reset_slow_injection() -> None:
    with _SLOW_LOCK:
        _SLOW_COUNTS.clear()
        _SLOW_RNGS.clear()


def slow_injection_counts() -> dict:
    """{site: units delayed} since the last reset (tests assert the
    injector actually fired)."""
    with _SLOW_LOCK:
        return dict(_SLOW_COUNTS)


def maybe_slow(site: str, conf: Optional[C.RapidsConf] = None,
               executor_id: Optional[str] = None) -> float:
    """Delay-injection hook, called once per unit of work at each
    instrumented site.  When `faultInjection.slowSite` names this site
    (and `slowVictim`, if set, names this executor), sleeps
    (slowFactor - 1) x slowUnitMs with seeded +/-25% jitter — a
    deterministic model of a degraded peer.  The sleep is cancellable:
    a losing speculative/hedged attempt parked here wakes the moment
    its AttemptToken fires.  Returns the injected delay (0 = none)."""
    conf = conf if conf is not None else C.get_active_conf()
    target = str(conf[C.SLOW_INJECT_SITE])
    if not target or target != site:
        return 0.0
    factor = float(conf[C.SLOW_INJECT_FACTOR])
    if factor <= 1.0:
        return 0.0
    victim = str(conf[C.SLOW_INJECT_VICTIM])
    if victim and executor_id is not None and victim != str(executor_id):
        return 0.0
    import random
    seed = int(conf[C.SLOW_INJECT_SEED])
    with _SLOW_LOCK:
        rng = _SLOW_RNGS.get((factor, seed))
        if rng is None:
            rng = _SLOW_RNGS[(factor, seed)] = random.Random(seed)
        jitter = 0.75 + 0.5 * rng.random()
        _SLOW_COUNTS[site] = _SLOW_COUNTS.get(site, 0) + 1
    unit_s = float(conf[C.SLOW_INJECT_UNIT_MS]) / 1e3
    delay = min((factor - 1.0) * unit_s * jitter, _SLOW_HARD_CAP_S)
    if delay > 0:
        cancellable_sleep(delay)
    return delay
