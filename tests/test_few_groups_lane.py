"""The grouped kernel's few-groups body (`exec/aggregate.py`): a batch
with at most `FEW_GROUPS_MAX` groups is grouped by exact key equality
(`ops/sort_encode.elect_group_leaders`) and reduced a slot at a time
under the slot's mask, with no sort, gather or scan; a batch with more
runs the sort body inside the same program.

Every case is held to the sort body alone (the class constant at 0: no
cond is built) and to pandas, and the exec's counter says which body
each batch took.
"""
from __future__ import annotations

import math

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.aggregate import AggMode, HashAggregateExec
from spark_rapids_tpu.exec.basic import LocalBatchSource
from spark_rapids_tpu.exprs.aggregates import (
    Average, Count, First, Last, Max, Min, Sum, VarianceSamp)
from spark_rapids_tpu.exprs.base import col, lit
from spark_rapids_tpu.utils import checks as CK
from spark_rapids_tpu.utils import metrics as M

N = 1000                # rows a batch (capacity 1,024)
BATCHES = 3             # so a merge phase follows the updates
#: the conf the reference's TPC harness runs, with no lane switch set
DEFAULTS = {"spark.rapids.sql.variableFloatAgg.enabled": True,
            "spark.rapids.sql.incompatibleOps.enabled": True,
            "spark.rapids.sql.test.enabled": True}


# ---- data ---------------------------------------------------------------
def _key_values(kind: str, codes: np.ndarray):
    """{column: (values, dtype or None, validity or None)} for group
    codes 0..g-1: distinct codes give distinct keys of the kind."""
    if kind == "string":
        return {"k": (np.array([f"key-{c:04d}" for c in codes], object),
                      None, None)}
    if kind == "two-strings":
        # unequal lengths sharing a prefix: "a", "ab", "abc", ... in the
        # first column, the second tells codes of one length apart
        return {"k": (np.array(["abcdefgh"[:1 + c % 5] for c in codes],
                               object), None, None),
                "k2": (np.array([f"{c // 5}" for c in codes], object),
                       None, None)}
    if kind == "long-string":
        # past one packed word, lengths that are no multiple of four,
        # and keys that differ in their last byte or their length only
        return {"k": (np.array(["abcdefghij" + "xyz"[c % 3] * (1 + c // 3)
                                for c in codes], object), None, None)}
    if kind == "int32":
        return {"k": ((codes * 7 - 3).astype(np.int32), None, None)}
    if kind == "int64-nulls":
        # code 0 is the NULL key (its stored value differs row by row)
        vals = (codes.astype(np.int64) << 33) + 5
        vals[codes == 0] = np.arange((codes == 0).sum())
        return {"k": (vals, None, codes != 0)}
    if kind == "float64-nan":
        # code 0: NaN; code 1: 0.0 and -0.0 (one group); the rest plain
        vals = codes.astype(np.float64) * 1.5
        vals[codes == 0] = np.nan
        zero = np.flatnonzero(codes == 1)
        vals[zero] = 0.0
        vals[zero[::2]] = -0.0
        return {"k": (vals, None, np.ones(len(codes), bool))}
    if kind == "date32":
        return {"k": ((9000 + codes * 31).astype(np.int32), T.DATE32, None)}
    if kind == "bool":
        return {"k": ((codes % 2).astype(bool), None, None)}
    raise AssertionError(kind)


def _batches(kind: str, groups: int, seed: int = 7, rows: int = N,
             batches: int = BATCHES):
    """`batches` batches of `rows` rows whose keys take `groups` values
    (every batch sees all of them when rows >= groups), a FLOAT64
    measure `v` with nulls and an INT32 one `w`; also the rows as a
    frame with the group's code in `g`."""
    rng = np.random.default_rng(seed)
    out, frames = [], []
    for _ in range(batches):
        codes = rng.permutation(np.arange(rows) % max(groups, 1))
        v = rng.uniform(1e4, 1e5, rows)
        v_ok = rng.random(rows) > 0.1
        w = rng.integers(-1000, 1000, rows).astype(np.int32)
        data, validity, fields = {}, {}, []
        for name, (vals, dt, ok) in _key_values(kind, codes).items():
            data[name] = vals
            if ok is not None:
                validity[name] = ok
            if dt is not None:
                fields.append((name, dt))
        data["v"], validity["v"], data["w"] = v, v_ok, w
        schema = None
        if fields:
            probe = ColumnarBatch.from_numpy(data, validity=validity)
            over = dict(fields)
            schema = T.Schema(tuple(
                T.Field(f.name, over.get(f.name, f.dtype))
                for f in probe.schema.fields))
        out.append(ColumnarBatch.from_numpy(data, schema, validity))
        frames.append(pd.DataFrame({
            "g": codes, "v": np.where(v_ok, v, np.nan), "w": w}))
    return out, pd.concat(frames, ignore_index=True)


def _keys_of(kind: str):
    return [col("k"), col("k2")] if kind == "two-strings" else [col("k")]


# ---- running and comparing ----------------------------------------------
def _collect(batches, keys, funcs, mode=AggMode.COMPLETE):
    agg = HashAggregateExec(keys, funcs, LocalBatchSource([batches]),
                            mode=mode)
    with C.session(C.RapidsConf(DEFAULTS)):
        out = agg.collect()
        rows = out.to_pylist()
    return agg, rows, out


def _few(agg):
    """(batches offered the few-groups body, batches that took it)."""
    return (int(agg.metrics.value(M.NUM_FEW_GROUPS_OFFERED)),
            int(agg.metrics.value(M.NUM_FEW_GROUP_BATCHES)))


def _norm(x):
    if x is None:
        return ("null",)
    if isinstance(x, float):
        if math.isnan(x):
            return ("nan",)
        return x + 0.0          # -0.0 and 0.0 are one key
    return x


def _by_key(rows, key_names):
    out = {}
    for r in rows:
        k = tuple(_norm(r[n]) for n in key_names)
        assert k not in out, f"group {k} came out twice"
        out[k] = {n: v for n, v in r.items() if n not in key_names}
    return out


def _same(got, want, rel=1e-12):
    assert set(got) == set(want)
    for k in want:
        assert set(got[k]) == set(want[k])
        for name, w in want[k].items():
            g = got[k][name]
            if w is None or g is None:
                assert g is None and w is None, (k, name, g, w)
            elif isinstance(w, float):
                if math.isnan(w):
                    assert math.isnan(g), (k, name, g, w)
                else:
                    assert g == pytest.approx(w, rel=rel, abs=0), \
                        (k, name, g, w)
            else:
                assert g == w, (k, name, g, w)


def _both_bodies(monkeypatch, batches, keys, funcs, key_names):
    """The answer with the few-groups body built in and with the sort
    body alone, each by key; also the first run's exec."""
    agg, rows, _ = _collect(batches, keys, funcs)
    assert agg._lane == "few-or-sort"
    monkeypatch.setattr(HashAggregateExec, "FEW_GROUPS_MAX", 0)
    sort_agg, sort_rows, _ = _collect(batches, keys, funcs)
    monkeypatch.undo()
    assert sort_agg._lane == "sort-segment" and _few(sort_agg) == (0, 0)
    got, alone = _by_key(rows, key_names), _by_key(sort_rows, key_names)
    _same(got, alone)
    return agg, got


def _null_if_nan(x):
    return None if isinstance(x, float) and math.isnan(x) else x


#: name -> (function over `v`, pandas aggregation of the group's `v`)
FUNCS = {
    "sum": (lambda: Sum(col("v")), lambda s: s.sum(min_count=1)),
    "count": (lambda: Count(col("v")), lambda s: int(s.count())),
    "count-star": (lambda: Count(None), lambda s: int(s.size)),
    "average": (lambda: Average(col("v")), lambda s: s.mean()),
    "min": (lambda: Min(col("v")), lambda s: s.min()),
    "max": (lambda: Max(col("v")), lambda s: s.max()),
    "first": (lambda: First(col("v")), lambda s: s.iloc[0]),
    "first-ignore-nulls": (lambda: First(col("v"), ignore_nulls=True),
                           lambda s: s.dropna().iloc[0]),
    "last": (lambda: Last(col("v")), lambda s: s.iloc[-1]),
    "last-ignore-nulls": (lambda: Last(col("v"), ignore_nulls=True),
                          lambda s: s.dropna().iloc[-1]),
    "variance": (lambda: VarianceSamp(col("v")), lambda s: s.var(ddof=1)),
}


def _reference(frame, names):
    want = {}
    for g, rows in frame.groupby("g", sort=False):
        want[g] = {n: _null_if_nan(FUNCS[n][1](rows["v"])) for n in names}
    return want


def _recode(got, kind, groups):
    """The answer's keys turned back into the group codes of `_batches`
    (each kind's key is an injective function of the code)."""
    probe = np.arange(max(groups, 1))
    cols = list(_key_values(kind, probe).values())
    code_of = {}
    for c in probe:
        key = tuple(("null",) if ok is not None and not ok[c]
                    else _norm(vals[c].item() if hasattr(vals[c], "item")
                               else vals[c])
                    for vals, _dt, ok in cols)
        code_of.setdefault(key, int(c))
    return {code_of[k]: v for k, v in got.items()}


# ---- key types ------------------------------------------------------------
#: kind -> groups a batch has (bool keys have two values; the two-string
#: kind packs 10 codes into 5 prefixes x 2)
KEY_KINDS = {"string": 4, "two-strings": 10, "long-string": 9, "int32": 4,
             "int64-nulls": 5,
             "float64-nan": 6, "date32": 4, "bool": 2}


@pytest.mark.parametrize("kind", sorted(KEY_KINDS))
def test_key_type(kind, monkeypatch):
    groups = KEY_KINDS[kind]
    batches, frame = _batches(kind, groups)
    names = ["sum", "count-star", "min", "max"]
    funcs = [FUNCS[n][0]().alias(n) for n in names]
    key_names = ["k", "k2"] if kind == "two-strings" else ["k"]
    agg, got = _both_bodies(monkeypatch, batches, _keys_of(kind), funcs,
                            key_names)
    # the three updates and the merge of their partials all took it
    assert _few(agg) == (BATCHES + 1, BATCHES + 1)
    assert len(got) == groups
    if kind == "float64-nan":
        # -0.0 and 0.0 are ONE group, NaN is one group
        assert ("nan",) in {k[0] for k in got} and (0.0,) in got
    if kind == "int64-nulls":
        assert (("null",),) in got
    _same(_recode(got, kind, groups), _reference(frame, names))


# ---- group counts ---------------------------------------------------------
@pytest.mark.parametrize("groups,few", [
    (1, True), (4, True), (16, True), (17, False), (1000, False)])
def test_group_count_decides_the_body(groups, few, monkeypatch):
    batches, frame = _batches("int32", groups)
    names = ["sum", "average", "count"]
    funcs = [FUNCS[n][0]().alias(n) for n in names]
    agg, got = _both_bodies(monkeypatch, batches, [col("k")], funcs, ["k"])
    offered, took = _few(agg)
    assert offered == BATCHES + 1
    assert took == (offered if few else 0)
    assert len(got) == groups
    _same(_recode(got, "int32", groups), _reference(frame, names))


def test_every_row_filtered_is_no_group_and_the_few_body(monkeypatch):
    """A batch whose rows a mask all removed has no leader: zero rounds,
    zero groups, and the sort is not paid either."""
    batches, _ = _batches("string", 4, batches=2)
    import jax.numpy as jnp
    dead = [ColumnarBatch(b.schema, b.columns, None, b.checks,
                          sparse=jnp.zeros(b.capacity, bool))
            for b in batches]
    funcs = [Sum(col("v")).alias("s"), Count(None).alias("c")]
    agg, got = _both_bodies(monkeypatch, dead, [col("k")], funcs, ["k"])
    assert got == {}
    offered, took = _few(agg)
    assert offered == took and offered >= 2


# ---- functions, both phases ----------------------------------------------
@pytest.mark.parametrize("name", sorted(FUNCS))
def test_function(name, monkeypatch):
    batches, frame = _batches("string", 5, seed=11)
    # the INT32 maximum beside it keeps a lone Count or Sum off the
    # banded lane, which has no few-groups body
    funcs = [FUNCS[name][0]().alias(name), Max(col("w")).alias("w")]
    agg, got = _both_bodies(monkeypatch, batches, [col("k")], funcs, ["k"])
    assert _few(agg) == (BATCHES + 1, BATCHES + 1)
    want = _reference(frame, [name])
    for g, rows in frame.groupby("g"):
        want[g]["w"] = int(rows["w"].max())
    _same(_recode(got, "string", 5), want)


def test_an_all_null_group_sums_to_null(monkeypatch):
    batches, frame = _batches("int32", 3)
    nulled = []
    for b in batches:
        df = b.to_pandas()
        ok = (df["k"] != -3).to_numpy() & df["v"].notna().to_numpy()
        nulled.append(ColumnarBatch.from_numpy(
            {"k": df["k"].to_numpy(np.int32),
             "v": df["v"].fillna(0.0).to_numpy(np.float64)},
            validity={"v": ok}))
    names = ["sum", "min", "first-ignore-nulls", "count"]
    funcs = [FUNCS[n][0]().alias(n) for n in names]
    agg, got = _both_bodies(monkeypatch, nulled, [col("k")], funcs, ["k"])
    assert got[(-3,)] == {"sum": None, "min": None,
                          "first-ignore-nulls": None, "count": 0}
    assert _few(agg) == (BATCHES + 1, BATCHES + 1)


@pytest.mark.parametrize("phase", ["update", "merge"])
def test_partial_and_final_execs_take_it_in_their_own_phase(phase,
                                                            monkeypatch):
    """A PARTIAL exec's updates and a FINAL exec's merge of exchanged
    partials (q1's two aggregates); the partial's FLOAT64 sum column IS
    float64 on the device."""
    import jax.numpy as jnp
    batches, frame = _batches("two-strings", 10, seed=3)
    keys = [col("k"), col("k2")]
    funcs = [Sum(col("v")).alias("sum"), Average(col("v")).alias("average"),
             Count(None).alias("count-star")]
    partial, _, part = _collect(batches, keys, funcs, AggMode.PARTIAL)
    assert partial._lane == "few-or-sort"
    assert _few(partial) == (BATCHES + 1, BATCHES + 1)
    sums = [c for f, c in zip(part.schema.fields, part.columns)
            if f.name in ("sum#0", "average#0")]
    assert len(sums) == 2
    assert all(f.dtype == T.FLOAT64 for f in part.schema.fields
               if f.name in ("sum#0", "average#0"))
    assert all(c.data.dtype == jnp.float64 for c in sums)
    if phase == "update":
        return
    # the FINAL aggregate over partials of each batch on its own
    parts = []
    for b in batches:
        _, _, p = _collect([b], keys, funcs, AggMode.PARTIAL)
        parts.append(p)
    final = HashAggregateExec(
        [col("k"), col("k2")], funcs,
        LocalBatchSource([parts], parts[0].schema), mode=AggMode.FINAL)
    with C.session(C.RapidsConf(DEFAULTS)):
        rows = final.collect().to_pylist()
    assert final._lane == "few-or-sort"
    offered, took = _few(final)
    assert offered == took and offered >= BATCHES
    got = _recode(_by_key(rows, ["k", "k2"]), "two-strings", 10)
    _same(got, _reference(frame, ["sum", "average", "count-star"]))


def test_float64_sums_equal_the_reference_and_are_float64():
    """Magnitudes a float32 accumulator would lose: 1e-12 relative holds
    only if every addition was made in float64."""
    rng = np.random.default_rng(1)
    k = rng.integers(0, 4, 4096).astype(np.int32)
    v = rng.uniform(1e8, 1e9, 4096) + rng.uniform(0, 1e-3, 4096)
    batch = ColumnarBatch.from_numpy({"k": k, "v": v})
    agg, rows, out = _collect([batch], [col("k")],
                              [Sum(col("v")).alias("s")])
    assert _few(agg) == (1, 1)
    want = pd.DataFrame({"k": k, "v": v}).groupby("k")["v"].sum()
    for r in rows:
        assert r["s"] == pytest.approx(math.fsum(v[k == r["k"]]),
                                       rel=1e-13, abs=0)
        assert r["s"] == pytest.approx(want[r["k"]], rel=1e-12, abs=0)
    as32 = {g: float(np.sum(v[k == g].astype(np.float32)))
            for g in range(4)}
    assert any(abs(as32[r["k"]] - r["s"]) / r["s"] > 1e-9 for r in rows)


# ---- masks ------------------------------------------------------------------
def test_a_sparse_batch_s_dead_rows_neither_lead_nor_count(monkeypatch):
    """Dead rows carry keys no live row has (17 more groups): they must
    not lead a round, or the batch would overflow."""
    import jax.numpy as jnp
    live_b, frame = _batches("int32", 4, batches=2)
    noisy_b, _ = _batches("int32", 21, seed=9, batches=2)
    sparse, frames = [], []
    for lb, nb in zip(live_b, noisy_b):
        ldf, ndf = lb.to_pandas(), nb.to_pandas()
        keep = np.arange(N) % 3 != 0
        k = np.where(keep, ldf["k"].to_numpy(), ndf["k"].to_numpy() + 1000)
        v_ok = ldf["v"].notna().to_numpy()
        b = ColumnarBatch.from_numpy(
            {"k": k.astype(np.int32),
             "v": ldf["v"].fillna(0.0).to_numpy(np.float64)},
            validity={"v": v_ok})
        mask = np.zeros(b.capacity, bool)
        mask[:N] = keep
        sparse.append(ColumnarBatch(b.schema, b.columns, None, b.checks,
                                    sparse=jnp.asarray(mask)))
        frames.append(pd.DataFrame({
            "g": (ldf["k"].to_numpy() + 3) // 7,
            "v": ldf["v"].astype(float)})[keep])
    names = ["sum", "count-star", "last"]
    funcs = [FUNCS[n][0]().alias(n) for n in names]
    agg, got = _both_bodies(monkeypatch, sparse, [col("k")], funcs, ["k"])
    assert len(got) == 4 and _few(agg) == (3, 3)
    _same(_recode(got, "int32", 4),
          _reference(pd.concat(frames, ignore_index=True), names))


def _find_all(plan, name, out=None):
    out = [] if out is None else out
    if type(plan).__name__ == name:
        out.append(plan)
    for c in getattr(plan, "children", []):
        _find_all(c, name, out)
    return out


def test_a_fused_filter_s_rows_neither_lead_nor_count(monkeypatch):
    """The filter runs INSIDE the update kernel (a pre-stage): the 30
    key values it removes never reach a round."""
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF
    from spark_rapids_tpu.plan.nodes import (CpuAggregate, CpuFilter,
                                             CpuSource)
    from spark_rapids_tpu.plan.overrides import accelerate, collect
    rng = np.random.default_rng(2)
    rows = 6000
    x = rng.integers(0, 34, rows).astype(np.int64)
    df = pd.DataFrame({"k": np.array([f"k{c}" for c in x], object),
                       "x": x, "v": rng.uniform(1.0, 2.0, rows)})
    conf = C.RapidsConf(dict(BENCH_CONF, **DEFAULTS))

    def run():
        plan = accelerate(CpuAggregate(
            [col("k")], [Sum(col("v")).alias("s"), Count(None).alias("c")],
            CpuFilter(col("x") < lit(4),
                      CpuSource.from_pandas(df, num_partitions=2))), conf)
        return plan, collect(plan, conf)

    plan, answer = run()
    aggs = _find_all(plan, "HashAggregateExec")
    fused = [a for a in aggs if a._pre_stage is not None]
    assert fused and all(a._lane == "few-or-sort" for a in aggs)
    for a in aggs:
        offered, took = _few(a)
        assert offered == took and offered > 0
    monkeypatch.setattr(HashAggregateExec, "FEW_GROUPS_MAX", 0)
    _, alone = run()
    want = df[df.x < 4].groupby("k").agg(s=("v", "sum"), c=("v", "size"))
    for got in (answer, alone):
        got = got.sort_values("k", ignore_index=True)
        assert list(got["k"]) == list(want.index) and len(got) == 4
        assert (got["c"].to_numpy() == want["c"].to_numpy()).all()
        np.testing.assert_allclose(got["s"].to_numpy(),
                                   want["s"].to_numpy(), rtol=1e-12)


# ---- qualification, counter, syncs ------------------------------------------
def test_a_string_min_keeps_the_sort_segment_kernel():
    """`_MinMax._update_string` does its own lexsort over the sorted
    segments: such an exec builds no cond, in either phase."""
    batches, _ = _batches("int32", 4)
    strs = []
    for b in batches:
        df = b.to_pandas()
        strs.append(ColumnarBatch.from_numpy(
            {"k": df["k"].to_numpy(np.int32),
             "t": np.array([f"t{w % 97:02d}" for w in df["w"]], object)}))
    agg, rows, _ = _collect(strs, [col("k")], [Min(col("t")).alias("m"),
                                               Count(None).alias("c")])
    assert (agg._lane, agg._merge_exec._lane) == ("sort-segment",
                                                  "sort-segment")
    # a kernel with the cond built in says which body ran; this one
    # had nothing to say, in four calls
    assert _few(agg) == (0, 0)
    allrows = pd.concat([b.to_pandas() for b in strs])
    want = allrows.groupby("k")["t"].min()
    assert {r["k"]: r["m"] for r in rows} == dict(want)


def test_the_counter_counts_batches_and_reading_it_is_the_only_sync():
    """Two batches of 4 groups and one of 40: the kernel's word on which
    body ran stays on the device until the exec's metrics are read, in
    one stacked read."""
    few_b, _ = _batches("string", 4, batches=2)
    many_b, _ = _batches("string", 40, seed=5, batches=1)
    batches = [few_b[0], many_b[0], few_b[1]]
    funcs = [Sum(col("v")).alias("s")]
    agg = HashAggregateExec([col("k")], funcs, LocalBatchSource([batches]),
                            mode=AggMode.PARTIAL)
    with C.session(C.RapidsConf(DEFAULTS)):
        list(agg.execute_columnar())            # warm: compiles
        agg2 = HashAggregateExec([col("k")], funcs,
                                 LocalBatchSource([batches]),
                                 mode=AggMode.PARTIAL)
        before = CK.host_sync_sites()
        out = list(agg2.execute_columnar())
        ran = {s: n - before.get(s, 0)
               for s, n in CK.host_sync_sites().items()
               if n > before.get(s, 0)}
        assert "metrics.resolve" not in ran
        mid = CK.host_sync_sites()
        offered, took = _few(agg2)
        read = {s: n - mid.get(s, 0)
                for s, n in CK.host_sync_sites().items()
                if n > mid.get(s, 0)}
    assert read == {"metrics.resolve": 1}
    # three updates (two few, one of 40 groups) and the merge of their
    # 48 partial rows (40 groups: the sort body)
    assert (offered, took) == (4, 2)
    assert sum(b.num_rows for b in out) == 40
