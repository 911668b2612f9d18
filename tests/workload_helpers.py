"""Shared by the per-suite workload parity files (test_workloads_*.py):
the CPU-vs-accelerated runners and the table generators.  One file per
suite, TPC-DS in `TPCDS_PARTS`, so `--dist loadfile` can spread them
over workers (each workload query compiles tens of small kernels; 136
cases in one file pinned them all to one worker)."""
import glob
import os

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
#: TPCDS_PARTS-th query name starting at i.  The driver hands whole
#: files to six workers, so the run cannot end before its longest file
#: does: at 6 parts one file was 977 s of a 1,205 s cold wall, at 18 the
#: longest is q66 (363 s cold by itself) and five others (the table is
#: in docs/dev-guide.md, "The tier-1 suite's clock").  A part costs one
#: `tpcds_tables()`, under a second.
TPCDS_PARTS = 18

# safety valve for ultra-selective queries (5+ independent predicate
# chains, e.g. q91's demographics x buy-potential x gmt chain): at the
# current 20k fixture scale the round-3 sweep showed ALL queries
# non-empty, but a generator/rng change can legitimately push one of
# these to zero rows; parity is still asserted on whatever they return
ALLOW_EMPTY = {"q91"}


def tpcds_names(part: int) -> list:
    # a part without its file would drop its queries from the suite
    # without a sound: fail every part's collection instead
    files = glob.glob(os.path.join(os.path.dirname(__file__),
                                   "test_workloads_tpcds_*.py"))
    assert len(files) == TPCDS_PARTS and 0 <= part < TPCDS_PARTS, \
        f"{len(files)} test_workloads_tpcds_*.py files, part {part}, " \
        f"TPCDS_PARTS = {TPCDS_PARTS}"
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
