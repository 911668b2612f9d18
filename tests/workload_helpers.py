"""Shared by the per-suite workload parity files (test_workloads_*.py):
the CPU-vs-accelerated runners and the table generators.  One file per
suite, so `--dist loadfile` can spread the suites over workers (each
workload query compiles tens of small kernels; 136 cases in one file
pinned them all to one worker)."""
import numpy as np

from spark_rapids_tpu import config as C
from spark_rapids_tpu.models import tpcds_data, tpcds_queries
from spark_rapids_tpu.plan.overrides import accelerate, collect

from parity import compare_frames as compare  # noqa: F401  (re-export)


def tpu_conf():
    from spark_rapids_tpu.models.tpch_bench import BENCH_CONF
    return C.RapidsConf(dict(BENCH_CONF))


def run_cpu(build_plan, t):
    return build_plan(t, lambda p: p.collect()).collect()


def run_tpu(build_plan, t, conf=None):
    conf = conf or tpu_conf()

    def run(p):
        return collect(accelerate(p, conf), conf)
    return run(build_plan(t, run))


# -- TPC-DS -----------------------------------------------------------------
#: the TPC-DS suite runs as this many files; file i holds every
#: TPCDS_PARTS-th query name starting at i
TPCDS_PARTS = 6

# safety valve for ultra-selective queries (5+ independent predicate
# chains, e.g. q91's demographics x buy-potential x gmt chain): at the
# current 20k fixture scale the round-3 sweep showed ALL queries
# non-empty, but a generator/rng change can legitimately push one of
# these to zero rows; parity is still asserted on whatever they return
ALLOW_EMPTY = {"q91"}


def tpcds_names(part: int) -> list:
    return sorted(tpcds_queries.QUERIES)[part::TPCDS_PARTS]


def tpcds_tables():
    # 20k: the smallest scale where every faithful query's predicate
    # chain keeps support (swept in round 3)
    return tpcds_data.gen_tables(np.random.default_rng(3), 20000)


def check_tpcds_parity(tables, name):
    fn = tpcds_queries.QUERIES[name]
    expected = run_cpu(fn, tpcds_data.sources(tables, 2))
    if name not in ALLOW_EMPTY:
        assert len(expected) > 0, f"{name}: CPU result empty — data bug"
    got = run_tpu(fn, tpcds_data.sources(tables, 2))
    compare(expected, got, name)
