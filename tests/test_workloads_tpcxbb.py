"""TPCx-BB-like workload parity (reference `TpcxbbLikeSpark` golden
rule: CPU vs accelerated diff)."""
import numpy as np
import pytest

from spark_rapids_tpu.models import tpcxbb

from workload_helpers import compare, run_cpu, run_tpu


@pytest.fixture(scope="module")
def xbb_tables():
    return tpcxbb.gen_tables(np.random.default_rng(4), 4000)


@pytest.mark.parametrize("name", sorted(tpcxbb.QUERIES))
def test_tpcxbb_parity(xbb_tables, name):
    fn = tpcxbb.QUERIES[name]
    expected = run_cpu(fn, tpcxbb.sources(xbb_tables, 2))
    assert len(expected) > 0, f"{name}: CPU result empty — data bug"
    got = run_tpu(fn, tpcxbb.sources(xbb_tables, 2))
    compare(expected, got, name)
