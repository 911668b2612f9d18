"""Mortgage ETL workload parity (reference `MortgageSparkSuite` golden
rule: CPU vs accelerated diff)."""
import numpy as np
import pytest

from spark_rapids_tpu.models import mortgage
from spark_rapids_tpu.plan.overrides import accelerate, collect

from workload_helpers import compare, tpu_conf


@pytest.fixture(scope="module")
def mtg_tables():
    return mortgage.gen_tables(np.random.default_rng(5), loans=300,
                               months=12)


def test_mortgage_etl_parity(mtg_tables):
    expected = mortgage.etl_plan(
        mortgage.sources(mtg_tables, 2)).collect()
    assert len(expected) == 300
    conf = tpu_conf()
    got = collect(accelerate(
        mortgage.etl_plan(mortgage.sources(mtg_tables, 2)), conf), conf)
    compare(expected, got, "mortgage-etl")


def test_mortgage_summary_parity(mtg_tables):
    expected = mortgage.summary_plan(
        mortgage.sources(mtg_tables, 2)).collect()
    assert len(expected) > 0
    conf = tpu_conf()
    got = collect(accelerate(
        mortgage.summary_plan(mortgage.sources(mtg_tables, 2)), conf),
        conf)
    compare(expected, got, "mortgage-summary")


def test_mortgage_delinquency_feature_sanity(mtg_tables):
    out = mortgage.etl_plan(mortgage.sources(mtg_tables)).collect()
    assert set(out["delinquency_12"].unique()) <= {0, 1}
    assert (out["reporting_months"] == 12).all()
