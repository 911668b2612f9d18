"""TPC-DS-like workload parity, part 4 of 6 by query name (reference
`TpcdsLikeSpark` golden rule: CPU vs accelerated diff)."""
import pytest

import workload_helpers as W

RELEASE_CACHES_PER_TEST = True  # see conftest._bound_process_rss


@pytest.fixture(scope="module")
def ds_tables():
    return W.tpcds_tables()


@pytest.mark.parametrize("name", W.tpcds_names(4))
def test_tpcds_parity(ds_tables, name):
    W.check_tpcds_parity(ds_tables, name)
