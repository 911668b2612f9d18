"""TPC-DS-like workload parity, one part of `W.TPCDS_PARTS` by query
name; which part is this file's number (reference `TpcdsLikeSpark`
golden rule: CPU vs accelerated diff).  Every part is this same file:
the suite is cut into files because the driver hands out whole files
to its workers (`workload_helpers.TPCDS_PARTS` says why 18)."""
import pytest

import workload_helpers as W

RELEASE_CACHES_PER_TEST = True  # see conftest._bound_process_rss

PART = int(__name__.rsplit("_", 1)[1])


@pytest.fixture(scope="module")
def ds_tables():
    return W.tpcds_tables()


@pytest.mark.parametrize("name", W.tpcds_names(PART))
def test_tpcds_parity(ds_tables, name):
    W.check_tpcds_parity(ds_tables, name)
