"""CPU rehearsal of chip_smoke.py: its query/compare function and its
--chips 4 lane run here at a tiny scale, so a later PR cannot break the
script unnoticed.  The device assertion is the script's `main`, not the
functions': on the CPU backend `main` must refuse to run anything."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

from spark_rapids_tpu.models.tpch_data import gen_tables  # noqa: E402

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def tables():
    return gen_tables(np.random.default_rng(0), 20_000)


def test_run_and_check_default_queries(tables):
    lines = []
    stats = chip_smoke.run_and_check(
        tables, list(chip_smoke.DEFAULT_QUERIES), out=lines.append)
    assert [s["query"] for s in stats] == [6, 1, 3]
    for s, line in zip(stats, lines):
        assert json.loads(line)["smoke_query"]["query"] == s["query"]
        assert s["matches_cpu_reference"] and s["rows_out"] > 0
        assert s["kernel_builds_hot"] == 0, s  # hot run compiles nothing
        assert s["cold_s"] > 0 and s["hot_s"] > 0
        # off the chip no Mosaic kernel may be claimed
        assert s["mosaic_kernels_traced"] == {}


def test_wrong_answer_fails(tables, monkeypatch):
    from spark_rapids_tpu.models import tpch_bench
    real = tpch_bench.run_query

    def skewed(n, tables, engine="tpu", **kw):
        out = real(n, tables, engine=engine, **kw)
        if engine == "cpu":
            out = out.copy()
            last = out.columns[-1]
            out[last] = out[last] * 1.01 + 1
        return out
    monkeypatch.setattr(tpch_bench, "run_query", skewed)
    with pytest.raises(AssertionError):
        chip_smoke.run_and_check(tables, [6], out=lambda s: None)


def test_cpu_island_fails(tables):
    """test.enabled is part of the smoke's conf: a plan node left on the
    pandas interpreter raises instead of hiding in the run."""
    conf = chip_smoke.smoke_conf(
        {"spark.rapids.sql.exec.CpuFilter": False})
    assert conf.get("spark.rapids.sql.test.enabled") is True
    with pytest.raises(AssertionError, match="did not run on the TPU"):
        chip_smoke.run_and_check(tables, [6], conf=conf,
                                 out=lambda s: None)


def test_mesh_exchange_lane_on_virtual_devices(tables):
    s = chip_smoke.run_mesh_exchange(tables, 4, out=lambda s: None)
    assert s["mesh_exchanges"] > 0
    assert all(ids == [0, 1, 2, 3]
               for ids in s["devices_holding_shards_per_exchange"])
    assert s["matches_cpu_reference"] and s["matches_one_device"]
    # one partition a chip after every exchange, and joined there
    assert s["devices_holding_partitions_after_exchange"] == [0, 1, 2, 3]
    assert s["devices_named_by_join_spans"] == [0, 1, 2, 3]
    assert list(s["cross_chip_moves"]) == ["topn-merge"]


def test_main_refuses_without_a_tpu():
    """JAX_PLATFORMS=cpu: non-zero exit before any query, and no result
    line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py", "--scale",
                        "1000"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "smoke_query" not in r.stdout
    assert "needs a TPU" in r.stderr
