"""Deferred device-side validity checks for optimistic fast paths.

A fast path (e.g. the dictionary group-by window) may produce results
whose validity is only known on device (a bool scalar: True = INVALID).
Syncing per batch blocks the host on the device, so checks
ride along until a host exit (collect / to_pandas / serde), where they
are verified in one async readback wave together with the result data.

On failure, `FastPathInvalid` carries recovery callbacks that disable
the originating fast path; `TpuExec.collect`/`plan.collect` catch it,
recover, and re-execute the (pure) plan once — the optimistic-
optimization-with-deopt discipline.

Checks attach to batches (`ColumnarBatch.checks`) AND register in a
process-wide pending list, so a plan whose intermediate execs drop the
per-batch tuple still fails safe at the next `verify_pending` boundary.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# debug host-sync counter (the pipelining PR's audit instrument): every
# device->host readback on the hot path calls note_host_sync(site), so
# "how many times per partition does the host block on the device" is a
# measurable number — the benchmark reports it as `host_syncs` and a
# regression shows up as a counter diff, not a mystery slowdown.
# Counting is always on: a sync costs a blocking device round trip, so
# one guarded dict increment per sync is noise.
_SYNC_LOCK = threading.Lock()
_SYNC_SITES: "collections.Counter" = collections.Counter()
_SYNC_BYTES: "collections.Counter" = collections.Counter()


def note_host_sync(site: str = "?", nbytes: int = 0) -> None:
    """Record one device->host blocking readback attributed to `site`.
    `nbytes` (when the site knows it) feeds the per-site byte counter
    AND the current query's data-movement ledger (readback edge), so
    control-plane syncs show up in the movement report next to the
    bulk collect/serde readbacks."""
    with _SYNC_LOCK:
        _SYNC_SITES[site] += 1
        if nbytes:
            _SYNC_BYTES[site] += nbytes
    if nbytes:
        from spark_rapids_tpu.utils import movement as MV
        led = MV.ledger()
        if led is not None:
            led.record(MV.EDGE_READBACK, nbytes, site=site)


def host_sync_count() -> int:
    with _SYNC_LOCK:
        return sum(_SYNC_SITES.values())


def host_sync_sites() -> dict:
    """Per-site sync counts (copy) — the audit view."""
    with _SYNC_LOCK:
        return dict(_SYNC_SITES)


def host_sync_bytes() -> dict:
    """Per-site readback byte counts for the sites that report them
    (copy) — the movement-ledger companion to host_sync_sites."""
    with _SYNC_LOCK:
        return dict(_SYNC_BYTES)


def reset_host_syncs() -> None:
    with _SYNC_LOCK:
        _SYNC_SITES.clear()
        _SYNC_BYTES.clear()


# ---------------------------------------------------------------------------
# cross-chip move counter, beside the host-sync counter: under an active
# mesh a partition lives on its own chip, and the only way a batch
# changes chips outside the all-to-all is `parallel/mesh.to_one_chip`,
# which counts here.  One move = one call that found something on
# another chip (a plan's single-partition point), whatever it carried.
_MOVE_SITES: "collections.Counter" = collections.Counter()
_MOVE_BYTES: "collections.Counter" = collections.Counter()


def note_cross_chip_move(site: str, nbytes: int) -> None:
    with _SYNC_LOCK:
        _MOVE_SITES[site] += 1
        _MOVE_BYTES[site] += nbytes


def cross_chip_moves() -> int:
    with _SYNC_LOCK:
        return sum(_MOVE_SITES.values())


def cross_chip_move_sites() -> dict:
    """{site: (moves, bytes)} (copy): the audit view."""
    with _SYNC_LOCK:
        return {k: (n, _MOVE_BYTES[k]) for k, n in _MOVE_SITES.items()}


def reset_cross_chip_moves() -> None:
    with _SYNC_LOCK:
        _MOVE_SITES.clear()
        _MOVE_BYTES.clear()


@dataclasses.dataclass(frozen=True, eq=False)
class BatchCheck:
    # eq=False: identity equality/hash.  The generated field-tuple
    # __eq__ would compare `flag` — a device array — so any list
    # membership test (e.g. _PENDING.remove) would dispatch an eq
    # kernel and BLOCK on a D2H sync.
    flag: object                      # device bool scalar; True = invalid
    origin: str                       # human-readable fast-path name
    recover: Optional[Callable] = None  # disables the fast path
    #: factory for a FATAL error (e.g. ANSI overflow): raised directly
    #: instead of the deopt-and-retry FastPathInvalid
    error: Optional[Callable] = None

    #: memoized verify outcome (class attr, not a dataclass field, so
    #: eq/hash semantics are untouched): a check rides on both the
    #: pending registry AND batch tuples, so without memoization the
    #: same flag is read back at every verify boundary it reaches —
    #: each a full device round trip
    _resolved = None

    def _memoize(self, bad: bool) -> None:
        object.__setattr__(self, "_resolved", bool(bad))


class FastPathInvalid(Exception):
    def __init__(self, checks):
        self.checks = list(checks)
        super().__init__(
            "optimistic fast path produced invalid results: "
            + ", ".join(c.origin for c in self.checks))

    def recover_all(self) -> None:
        for c in self.checks:
            if c.recover is not None:
                c.recover()


_LOCK = threading.Lock()
_PENDING: list[BatchCheck] = []

_RETRY = threading.local()


def _pending_list() -> list:
    """The deferred-check registry for the CURRENT query: each
    QueryContext owns its own list (concurrent queries' checks must
    not interleave — one query's snapshot/drain would steal another's
    checks); the process-global list serves query-less legacy paths."""
    try:
        from spark_rapids_tpu.exec import scheduler as S
        qc = S.current()
        if qc is not None:
            return qc.pending_checks
    except ImportError:
        pass
    return _PENDING


def set_retrying(flag: bool) -> None:
    """Marks the deopt RE-EXECUTION (collect catches FastPathInvalid,
    recovers, and re-runs once).  Optimistic fast paths whose recovery
    is 'escalate a learned parameter' must produce guaranteed-valid
    results during the retry — there is no second retry — and consult
    this to bypass themselves for that one execution."""
    _RETRY.flag = flag


def is_retrying() -> bool:
    return getattr(_RETRY, "flag", False)


def register_deopt(flag, origin: str, recover, checks: tuple) -> tuple:
    """Append a deferred deopt check to a batch's check tuple (shared
    by the aggregate and window hash-grouping lanes).  `flag` None
    means the fast lane was not taken — nothing to check."""
    if flag is None:
        return checks
    return checks + (register(BatchCheck(flag, origin, recover)),)


def register(check: BatchCheck) -> BatchCheck:
    with _LOCK:
        _pending_list().append(check)
    return check


def _stack_int32(*items):
    return jnp.stack([jnp.asarray(f).astype(jnp.int32).reshape(())
                      for f in items])


_stack_int32.__name__ = "checks_stack"         # its name on the device
_STACK = jax.jit(_stack_int32)
#: the least arity of `_STACK`.  A call's arity is padded up to a power
#: of two by repeating its LAST item, so the operands' dtypes and
#: placements past the real ones follow the real ones: a run of one
#: kind (bool flags) then compiles one program a width, and 2-300 such
#: items at most seven.  (A padding constant of its own dtype would
#: make the operands' signature, and so the program, one an arity.)
_STACK_MIN = 8


def _device_key(f):
    try:
        return frozenset(f.devices())
    except Exception:
        return None


def _read_group(values: list) -> np.ndarray:
    """The host values of one device group's arrays, in one blocking
    read: one item read directly, more stacked as int32 by `_STACK` (one
    dispatch).  The padding repeats an item of the group, so it is on
    the group's devices."""
    note_host_sync("checks.verify", nbytes=4 * len(values))
    if len(values) == 1:
        return np.asarray(values[0]).reshape(1)
    width = max(_STACK_MIN, 1 << (len(values) - 1).bit_length())
    return np.asarray(_STACK(*values, *[values[-1]] * (width - len(values))))


def verify(checks, scalars=(), tally: Optional[dict] = None) -> list:
    """Resolve the given checks now (syncs); raise on any failure.

    A check given more than once (a drain hands over a batch's checks
    AND the registry, which hold the same ones) is read once: checks
    are taken distinct by identity, in first-seen order, and one
    already resolved is not read again.  The unresolved device flags
    are read in ONE dispatch and ONE device-to-host transfer per device
    group (single-chip: exactly one): `_STACK` casts each to an int32
    scalar and stacks them, where casting and stacking eagerly cost
    about three dispatches a flag.  A group of one item is read
    directly.  Flags whose group does not stack (e.g. sharded across a
    mesh) fall back to per-flag readback.

    `scalars`: extra device int scalars (e.g. a collect's lazy output
    row count) that ride the SAME stacked readback — the host-sync diet
    for the collect boundary, which otherwise pays a second full round
    trip reading the row count right after the flag wave.  They are
    positional and never made distinct.  Returns their host values
    (ints), in order.

    `tally`, when given, receives `checks_given` (the checks handed in)
    and `checks_read` (the distinct unresolved checks this call read);
    both are known on the host."""
    checks = list(checks)
    given = len(checks)
    checks = list(dict.fromkeys(checks))
    scalars = list(scalars)
    scalar_vals: list = [None] * len(scalars)
    unresolved = [c for c in checks if c._resolved is None]
    if tally is not None:
        tally["checks_given"] = given
        tally["checks_read"] = len(unresolved)
    if not checks and not scalars:
        return scalar_vals
    # scalars first: a group's last item, which the padding repeats, is
    # then a flag wherever there are flags
    device_items = []
    for j, s in enumerate(scalars):
        if hasattr(s, "devices") or hasattr(s, "sharding"):
            device_items.append((j, s))
        else:
            scalar_vals[j] = int(np.asarray(s))
    for c in unresolved:
        f = c.flag
        if hasattr(f, "devices") or hasattr(f, "sharding"):
            device_items.append((c, f))
        else:
            c._memoize(bool(np.asarray(f)))

    def resolve(owner, v) -> None:
        if isinstance(owner, int):
            scalar_vals[owner] = int(v)
        else:
            owner._memoize(bool(v))

    groups: dict = {}
    for owner, f in device_items:
        groups.setdefault(_device_key(f), []).append((owner, f))
    for items in groups.values():
        try:
            values = _read_group([f for _, f in items])
        except Exception:
            # arbitrary placement (e.g. flags sharded across devices):
            # per-item readback still resolves correctly
            for owner, f in items:
                note_host_sync("checks.verify", nbytes=4)
                resolve(owner, np.asarray(f))
            continue
        for (owner, _), v in zip(items, values):
            resolve(owner, v)
    bad = [c for c in checks if c._resolved]
    if checks:
        done = set(checks)
        with _LOCK:
            pending = _pending_list()
            pending[:] = [c for c in pending if c not in done]
    for c in bad:
        if c.error is not None:
            raise c.error()
    if bad:
        raise FastPathInvalid(bad)
    return scalar_vals


def snapshot() -> int:
    """Mark the current registry position; checks registered after this
    belong to the enclosing execution attempt.  The registry is
    PER-QUERY (each QueryContext owns its list, helper threads reach it
    through their propagated context), so concurrent queries\'
    registrations never interleave and one query\'s drain can never
    steal another\'s checks."""
    with _LOCK:
        return len(_pending_list())


def drain_since(mark: int) -> list:
    """Remove and return every check the current query registered
    after `mark`."""
    with _LOCK:
        pending = _pending_list()
        checks = pending[mark:]
        del pending[mark:]
    return checks


def verify_pending() -> None:
    """Resolve EVERY outstanding registered check (the collect-boundary
    safety net for execs that dropped per-batch check tuples)."""
    with _LOCK:
        checks = list(_pending_list())
    verify(checks)


def clear_pending() -> None:
    with _LOCK:
        del _pending_list()[:]
