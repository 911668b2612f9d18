"""`utils/checks.verify`, the collect boundary's read of a query's
deferred check flags: each distinct flag is read once, all of a device's
unresolved flags in ONE jitted stack (one dispatch, one counted host
sync), a lone item directly, and the stack's arity padded to a power of
two so that few programs compile.  What a failing flag raises is as it
was."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.utils import checks as CK


@pytest.fixture(autouse=True)
def clean_registry():
    CK.clear_pending()
    yield
    CK.clear_pending()


class _Counted:
    """A stand-in for `CK._STACK` that counts its dispatches."""

    def __init__(self, program):
        self.program = program
        self.calls = []

    def __call__(self, *items):
        self.calls.append(len(items))
        return self.program(*items)


@pytest.fixture
def stack(monkeypatch):
    counted = _Counted(CK._STACK)
    monkeypatch.setattr(CK, "_STACK", counted)
    return counted


def _syncs() -> int:
    return CK.host_sync_sites().get("checks.verify", 0)


def _flags(n, bad=(), device=None):
    """`n` registered checks whose flags are device bool scalars, those
    at the indices in `bad` true."""
    out = []
    for i in range(n):
        flag = jnp.asarray(i in bad)
        if device is not None:
            flag = jax.device_put(flag, device)
        out.append(CK.register(CK.BatchCheck(flag, f"path{i}")))
    return out


def test_each_flag_given_twice_is_read_once_in_one_dispatch(stack):
    checks = _flags(200)
    tally: dict = {}
    before = _syncs()
    assert CK.verify(checks + checks, tally=tally) == []
    assert stack.calls == [256]             # 200 padded to a power of two
    assert _syncs() - before == 1
    assert tally == {"checks_given": 400, "checks_read": 200}
    assert all(c._resolved is False for c in checks)
    assert CK.snapshot() == 0               # the registry let them go


def test_one_bad_flag_among_duplicates_names_only_its_origin(stack):
    rng = np.random.default_rng(39)
    bad = int(rng.integers(0, 95))
    checks = _flags(95, bad={bad})
    given = checks + list(reversed(checks))
    with pytest.raises(CK.FastPathInvalid) as e:
        CK.verify(given)
    assert [c.origin for c in e.value.checks] == [f"path{bad}"]
    assert str(e.value).endswith(f": path{bad}")
    assert len(stack.calls) == 1
    assert CK.snapshot() == 0


def test_a_fatal_check_raises_its_own_error(stack):
    class Overflow(ArithmeticError):
        pass
    ok = _flags(3)
    fatal = CK.register(CK.BatchCheck(jnp.asarray(True), "ansiAdd",
                                      error=lambda: Overflow("ansi")))
    with pytest.raises(Overflow, match="ansi"):
        CK.verify(ok + [fatal] + ok)
    assert len(stack.calls) == 1


def test_scalars_come_back_in_order_beside_the_checks(stack):
    checks = _flags(5)
    scalars = [jnp.int32(7), np.int32(-3), jnp.int32(11), jnp.int32(7)]
    before = _syncs()
    assert CK.verify(checks + checks, scalars=scalars) == [7, -3, 11, 7]
    # 5 flags + the 3 device scalars (the host one is read on the host;
    # equal scalars stay positional), padded to 8
    assert stack.calls == [8] and _syncs() - before == 1


def test_a_single_item_is_read_without_the_stack(monkeypatch):
    def refuse(*_a):
        raise AssertionError("a lone item went through the stack")
    monkeypatch.setattr(CK, "_STACK", refuse)
    (check,) = _flags(1)
    tally: dict = {}
    before = _syncs()
    CK.verify([check, check], tally=tally)
    assert check._resolved is False and _syncs() - before == 1
    assert tally == {"checks_given": 2, "checks_read": 1}
    assert CK.verify([], scalars=[jnp.int32(41)]) == [41]
    assert _syncs() - before == 2


def test_resolved_checks_are_not_read_again(stack):
    checks = _flags(20, bad={3})
    with pytest.raises(CK.FastPathInvalid):
        CK.verify(checks)
    before = _syncs()
    tally: dict = {}
    with pytest.raises(CK.FastPathInvalid) as e:
        CK.verify(checks + checks, tally=tally)
    assert [c.origin for c in e.value.checks] == ["path3"]
    assert tally == {"checks_given": 40, "checks_read": 0}
    assert len(stack.calls) == 1 and _syncs() == before
    # a fresh flag beside the resolved ones is the only one read
    (fresh,) = _flags(1)
    CK.verify(checks[:3] + [fresh], tally=tally)
    assert tally["checks_read"] == 1 and _syncs() == before + 1


def test_host_flags_are_read_on_the_host(stack):
    host = [CK.BatchCheck(np.bool_(False), "hostPath"),
            CK.BatchCheck(False, "plain")]
    bad = CK.BatchCheck(np.bool_(True), "hostBad")
    before = _syncs()
    with pytest.raises(CK.FastPathInvalid) as e:
        CK.verify(host + [bad] + host)
    assert [c.origin for c in e.value.checks] == ["hostBad"]
    assert stack.calls == [] and _syncs() == before


def test_arities_two_to_three_hundred_compile_at_most_seven_programs(
        monkeypatch):
    # a function of its own: jit wrappers of one function share a cache
    fresh = jax.jit(lambda *items: CK._stack_int32(*items))
    monkeypatch.setattr(CK, "_STACK", fresh)
    flags = [jnp.asarray(False) for _ in range(300)]
    for n in range(2, 301):
        checks = [CK.BatchCheck(f, "p") for f in flags[:n]]
        CK.verify(checks)
    assert fresh._cache_size() == 7         # 8, 16, ..., 512


def test_flags_on_four_devices_are_read_a_group_a_device(stack):
    devices = jax.devices()[:4]
    assert len(devices) == 4, "conftest forces eight cpu devices"
    checks = [c for d in devices for c in _flags(6, device=d)]
    bad = CK.register(CK.BatchCheck(
        jax.device_put(jnp.asarray(True), devices[2]), "onChip2"))
    rows = jax.device_put(jnp.int32(1234), devices[3])
    before = _syncs()
    with pytest.raises(CK.FastPathInvalid) as e:
        CK.verify(checks + [bad] + checks, scalars=[rows])
    assert [c.origin for c in e.value.checks] == ["onChip2"]
    assert stack.calls == [8] * 4 and _syncs() - before == 4
    assert all(c._resolved is False for c in checks)
    # and with nothing bad, the scalar comes back from its chip
    ok = [c for d in devices for c in _flags(3, device=d)]
    assert CK.verify(ok + ok, scalars=[rows]) == [1234]


def test_a_flag_sharded_across_chips_is_still_read():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
    replicated = NamedSharding(mesh, PartitionSpec())
    flags = [jax.device_put(jnp.asarray(v), replicated)
             for v in (False, True, False)]
    checks = [CK.BatchCheck(f, f"sharded{i}") for i, f in enumerate(flags)]
    with pytest.raises(CK.FastPathInvalid) as e:
        CK.verify(checks + checks)
    assert [c.origin for c in e.value.checks] == ["sharded1"]
