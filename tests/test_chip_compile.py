"""Main-path kernels compile for the chip: the three Pallas kernels at
the engine's shapes and the planner's XLA kernels at the batch bucket an
SF1 run dispatches (`spark.rapids.tpu.batchMaxRows` = 65536), lowered
and compiled for a DESCRIBED v5e (`jax.experimental.topologies`) with no
chip attached.  A compile that passes is not a chip run — it says the
TPU compiler accepts the program (tiling, VMEM, Mosaic legalization),
nothing about results or times; `chip_smoke.py` is the run.

All of these live in this ONE file: only one process at a time may load
the TPU library, so the topology is described inside a module-scoped
fixture (never at import) and every compile happens in this process.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

CAP = 1 << 16  # conf batchMaxRows: the scan batch bucket of an SF1 run


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the described chip, with the persistent compile cache
    off for the module: a compile for a described device is written to
    the cache but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    assert topo.devices[0].device_kind == "TPU v5 lite"
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args, **kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    return compiled.as_text()


# -- the three Pallas (Mosaic) kernels, called as the engine calls them ----
def test_window_group_sums_compiles_for_v5e(one_chip):
    """exec/aggregate.py's dictionary-window aggregate: seg ids + f32
    measures at the batch bucket, `interpret=False` as on the chip."""
    from spark_rapids_tpu.ops.grouped_window import window_group_sums
    n_measures, out_cap = 10, 1 << 14
    text = _compile(
        window_group_sums,
        _spec(one_chip, (CAP,), jnp.int32),
        tuple(_spec(one_chip, (CAP,), jnp.float32)
              for _ in range(n_measures)),
        out_cap=out_cap, capacity=CAP, interpret=False)
    assert "tpu_custom_call" in text


def test_grouped_sum_pallas_compiles_for_v5e(one_chip):
    """The dict lane of HashAggregateExec: slots in a 1024-wide window
    plus the null and overflow sentinels (n_groups = g_pad + 1)."""
    from spark_rapids_tpu.ops.pallas_kernels import grouped_sum_pallas
    text = _compile(
        grouped_sum_pallas,
        _spec(one_chip, (CAP,), jnp.int32),
        tuple(_spec(one_chip, (CAP,), jnp.float32) for _ in range(2)),
        _spec(one_chip, (), jnp.int32),
        n_groups=1025, capacity=CAP, interpret=False)
    assert "tpu_custom_call" in text


def test_q1_fused_pallas_compiles_for_v5e(one_chip):
    """models/tpch.py's stacked Q1 step: 8 batches of 2M rows in one
    dispatch."""
    from spark_rapids_tpu.models.tpch import Q1_CUTOFF_DAYS
    from spark_rapids_tpu.ops.pallas_kernels import q1_fused_pallas
    cap, batch_rows = 1 << 24, 1 << 21
    i32 = _spec(one_chip, (cap,), jnp.int32)
    f32 = _spec(one_chip, (cap,), jnp.float32)
    text = _compile(
        q1_fused_pallas, i32, i32, f32, f32, f32, f32, i32,
        _spec(one_chip, (cap // batch_rows,), jnp.int32),
        capacity=cap, cutoff=Q1_CUTOFF_DAYS, batch_rows=batch_rows,
        interpret=False)
    assert "tpu_custom_call" in text


# -- the planner's XLA kernels, captured from a real planner run ----------
class _Recorder:
    """Wraps every kernel the engine's KernelCache builds so its first
    call leaves (jitted fn, abstract args) behind."""

    def __init__(self):
        self.calls = []  # (cache key, jitted fn, args, kwargs)

    def wrap(self, key, fn):
        if not hasattr(fn, "lower"):
            return fn
        rec = self

        def abstract(x):
            if isinstance(x, (jax.Array, np.ndarray)):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        class Recorded:
            def __call__(self, *a, **k):
                rec.calls.append(
                    (key, fn) + jax.tree_util.tree_map(abstract, (a, k)))
                return fn(*a, **k)

            def __getattr__(self, name):
                return getattr(fn, name)

        return Recorded()


@pytest.fixture(scope="module")
def planner_kernels():
    """Run TPC-H q6, q1 and q3 through accelerate()+collect() on the CPU
    backend at a scale whose scan batches fill the 65536-row bucket, and
    capture what the engine jits."""
    from spark_rapids_tpu.exec import base as B
    from spark_rapids_tpu.models.tpch_bench import run_query
    from spark_rapids_tpu.models.tpch_data import gen_tables
    import chip_smoke

    rec = _Recorder()
    orig = B.KernelCache._build_watched

    def build_watched(key, builder, kp_entry=None):
        return rec.wrap(key, orig(key, builder, kp_entry))

    B.clear_kernel_cache()
    B.KernelCache._build_watched = staticmethod(build_watched)
    try:
        tables = gen_tables(np.random.default_rng(0), 100_000)
        for n in (6, 1, 3):
            run_query(n, tables, engine="tpu",
                      conf=chip_smoke.smoke_conf())
    finally:
        B.KernelCache._build_watched = staticmethod(orig)
        B.clear_kernel_cache()
    assert rec.calls
    return rec.calls


def _largest(calls, tag, smallest=False):
    """The recorded call of kernel family `tag` with the most rows (or
    the fewest: a sort network's compile time grows with its rows)."""
    def rows(call):
        leaves = [l for l in jax.tree_util.tree_leaves(call[2:])
                  if isinstance(l, jax.ShapeDtypeStruct) and l.shape]
        return max(l.shape[0] for l in leaves)
    hits = [c for c in calls if tag in repr(c[0]) or
            tag in getattr(c[1], "__qualname__", "")]
    assert hits, f"the planner run built no {tag!r} kernel"
    best = (min if smallest else max)(hits, key=rows)
    return best, rows(best)


@pytest.mark.parametrize("tag,min_rows", [
    ("_reduce_kernel", CAP),      # q6: fused scan->filter->project->sum
    ("_groupby_kernel", CAP),     # q1: aggregate update (sort-encode lane)
    ("_split_kernel_for", CAP),   # q3: exchange split of lineitem
    # q3's joins run partition by partition since PR 35: at this scale
    # each of two partitions builds from half the build side
    ("_build_dense_probe", CAP // 2),  # q3: join probe
    ("SortExec._kernel", 1),      # q3: TopN
    # q3's sort-path join, the dearest compile of a cold q3 on the chip
    # (PERF.md, PR 29: 83 s + 75 s of 248 s at SF0.25): its match (key
    # words + iota in one sort; the smaller of q3's two, since the
    # compile time grows with the rows: 133 s here at the 262,144 its
    # larger one had before PR 30 sized the build side by its rows) and
    # its pair expansion
    ("_match_kernel", -(1 << 13)),
    ("_expand_kernel", CAP // 2),
])
def test_planner_kernel_compiles_for_v5e(one_chip, planner_kernels, tag,
                                         min_rows):
    (key, fn, args, kwargs), rows = _largest(planner_kernels, tag,
                                             smallest=min_rows < 0)
    assert rows >= abs(min_rows), (tag, rows)

    def place(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=one_chip)
        return x

    args, kwargs = jax.tree_util.tree_map(place, (args, kwargs))
    compiled = fn.lower(*args, **kwargs).compile()
    assert compiled.memory_analysis() is not None


def test_upload_split_compiles_for_v5e(one_chip):
    """The source upload's split program at the largest shape a cell
    runs: an SF1 lineitem partition's 45 full chunks of q6's four
    columns (data, validity, float32 shadows: 11 arrays, 130 MB)."""
    from spark_rapids_tpu.columnar.batch import _split_chunks_jit
    rows = 45 * CAP
    dtypes = [jnp.int32, jnp.bool_] + 3 * [jnp.float64, jnp.bool_,
                                           jnp.float32]
    compiled = _split_chunks_jit.lower(
        [_spec(one_chip, (rows,), d) for d in dtypes], CAP).compile()
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert len(outs) == 45 * 11 and {o.shape for o in outs} == {(CAP,)}
    assert compiled.memory_analysis() is not None


def test_upload_split_of_string_columns_compiles_for_v5e(one_chip):
    """The same program over a run's string columns: q1's two
    one-character keys, 45 full chunks each (byte matrices laid flat at
    `char_cap` 8, validity, lengths), one chunk of one column at a
    longer bucket."""
    from spark_rapids_tpu.columnar.batch import _split_chunks_jit
    rows = 45 * CAP
    caps = [(8,) * 45, (8,) * 44 + (32,)]
    arrays, char_caps = [], []
    for cc in caps:
        arrays += [_spec(one_chip, (CAP * sum(cc),), jnp.uint8),
                   _spec(one_chip, (rows,), jnp.bool_),
                   _spec(one_chip, (rows,), jnp.int32)]
        char_caps += [cc, None, None]
    compiled = _split_chunks_jit.lower(arrays, CAP,
                                       tuple(char_caps)).compile()
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert len(outs) == 45 * 6
    assert {o.shape for o in outs} == {(CAP,), (CAP, 8), (CAP, 32)}
    assert compiled.memory_analysis() is not None


# -- the grouped kernel at the SMALL capacities a merge runs at -----------
@pytest.mark.parametrize("phase,cap", [
    ("merge", 256),     # q1 at SF1: a partition's 46 partials, 184 rows
    ("merge", 1024),
    ("update", 1024),
    ("update", 16384),  # the largest batch whose groups are not compacted
])
def test_grouped_kernel_compiles_at_small_capacities_for_v5e(one_chip,
                                                             phase, cap):
    """Both bodies of `jit_agg_update` / `jit_agg_merge` live in one
    `lax.cond`, and at a few hundred rows the compiler keeps a branch's
    buffers in VMEM: PR 36's first hand-in compiled at 65,536 rows here
    and failed ON THE CHIP at 256 (`masked_positions`' int64 cumsum, 19
    MB of scoped VMEM asked of 16).  q1's two string keys (the hash
    lane), FLOAT64 measures."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.aggregate import AggMode, HashAggregateExec
    from spark_rapids_tpu.exec.basic import LocalBatchSource
    from spark_rapids_tpu.exprs.aggregates import Average, Count, Sum
    from spark_rapids_tpu.exprs.base import col
    rng = np.random.default_rng(0)
    rows = {"k": np.array(["ANR"[i % 3] for i in range(cap)], object),
            "k2": np.array(["FO"[i % 2] for i in range(cap)], object),
            "v": rng.uniform(1, 2, cap), "w": rng.uniform(1, 2, cap)}
    funcs = [Sum(col("v")).alias("s"), Average(col("w")).alias("a"),
             Count(None).alias("c")]
    batch = ColumnarBatch.from_numpy(rows)
    agg = HashAggregateExec([col("k"), col("k2")], funcs,
                            LocalBatchSource([[batch]]),
                            mode=AggMode.PARTIAL)
    with C.session(C.RapidsConf({})):
        if phase == "merge":
            inter = agg._partial_schema()
            (part,) = list(agg.execute_columnar())
            batch = ColumnarBatch(inter, [c.with_capacity(cap)
                                          for c in part.columns],
                                  part.num_rows)
            exec_ = agg._get_merge_exec(inter)
        else:
            exec_ = agg
        kern = exec_._groupby_kernel(batch, phase,
                                     agg._kernel_compact_cap(batch))
    assert exec_._lane == "few-or-sort"

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    args = jax.tree_util.tree_map(place,
                                  (batch.columns, batch.num_rows_i32))
    compiled = getattr(kern, "_ck_fn", kern).lower(*args).compile()
    assert " conditional(" in compiled.as_text()
    assert compiled.memory_analysis() is not None


# -- the collect boundary's read of its deferred check flags ---------------
@pytest.mark.parametrize("width", [8, 128, 256])
def test_check_stack_compiles_for_v5e(one_chip, width):
    """`utils/checks._STACK` at the arities a cell dispatches (q1 at SF1:
    about 95 distinct flags and the row count, padded to 128; 8 the
    least): the row count, then bool flags, each cast to int32 and
    stacked."""
    from spark_rapids_tpu.utils import checks as CK
    args = [_spec(one_chip, (), jnp.int32)] + [
        _spec(one_chip, (), jnp.bool_) for _ in range(width - 1)]
    compiled = CK._STACK.lower(*args).compile()
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (width,) and out.dtype == jnp.int32
    assert compiled.memory_analysis() is not None
