"""TPC-H query runner (reference `TpcxbbLikeBench.runBench`
`TpcxbbLikeBench.scala:26-40` / `TpcdsLikeBench.scala`): one query on
the accelerated path or on the CPU engine, under the conf the reference
runs its TPC suites with.
"""
from __future__ import annotations

from typing import Optional

from spark_rapids_tpu import config as C
from spark_rapids_tpu.models.tpch_data import sources
from spark_rapids_tpu.models.tpch_queries import QUERIES


def _tpu_runner(conf):
    from spark_rapids_tpu.plan.overrides import accelerate, collect

    def run(plan):
        return collect(accelerate(plan, conf), conf)
    return run


def _cpu_runner():
    return lambda plan: plan.collect()


#: bench conf mirrors how the reference runs its TPC suites: incompat /
#: order-sensitive float aggregation enabled (results differ from CPU only
#: in float rounding order)
BENCH_CONF = {
    "spark.rapids.sql.variableFloatAgg.enabled": True,
    "spark.rapids.sql.incompatibleOps.enabled": True,
}


def run_query(n: int, tables, engine: str = "tpu",
              conf: Optional[C.RapidsConf] = None,
              num_partitions: int = 2):
    t = sources(tables, num_partitions)
    if engine == "cpu":
        run = _cpu_runner()
        return QUERIES[n](t, run).collect()
    conf = conf or C.RapidsConf(dict(BENCH_CONF))
    run = _tpu_runner(conf)
    return run(QUERIES[n](t, run))
