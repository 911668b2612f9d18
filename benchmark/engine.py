"""The system under test, and the only file of the benchmark that
imports it.  What it takes from the program: the entry
(`accelerate` + `collect`), the query plans, the table schemas, the
conf, and its counters.  Nothing here decides a number."""
from __future__ import annotations

import contextlib
import importlib
import time

COMPILE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """XLA compile requests and persistent-cache hits, from JAX's own
    monitoring events (a request that is not a hit compiled)."""

    def __init__(self):
        import jax.monitoring
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == COMPILE_REQUEST:
            self.requests += 1
        elif event == CACHE_HIT:
            self.hits += 1


class Engine:
    def __init__(self, config: dict, profile: bool):
        import spark_rapids_tpu  # noqa: F401  x64 and the compile cache
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.plan.overrides import accelerate, collect
        from spark_rapids_tpu.utils import checks
        self._accelerate, self._collect = accelerate, collect
        self._checks = checks
        settings = dict(config["conf"])
        if profile:
            settings["spark.rapids.sql.profile.enabled"] = True
        self.conf = C.RapidsConf(settings)
        self._builders = importlib.import_module(config["queries"]).QUERIES
        self._sources = importlib.import_module(config["sources"]).sources
        self.partitions = int(config["partitions"])
        self.mesh_chips = int(config.get("mesh_chips", 0))
        self.sources = None

    def register(self, tables: dict) -> None:
        """The session's tables: partitioned host frames, as a scan's
        files would lie ready.  Every query uploads from them anew."""
        self.sources = self._sources(tables, self.partitions)

    def release(self) -> None:
        self.sources = None

    @contextlib.contextmanager
    def session(self):
        """One partition per chip under an active mesh where the
        configuration says so; nothing otherwise."""
        if not self.mesh_chips:
            yield
            return
        from spark_rapids_tpu.parallel import mesh as M
        from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
        ShuffleExchangeExec._MESH_EXCHANGES_RUN = 0
        ShuffleExchangeExec._MESH_SHARD_DEVICES = []
        with M.active_mesh(M.make_mesh(self.mesh_chips)):
            yield

    def mesh_shard_devices(self) -> list:
        if not self.mesh_chips:
            return []
        from spark_rapids_tpu.shuffle.exchange import ShuffleExchangeExec
        return list(ShuffleExchangeExec._MESH_SHARD_DEVICES)

    def host_syncs(self) -> int:
        return self._checks.host_sync_count()

    def run(self, query: int, annotate=contextlib.nullcontext):
        """One query through the entry: plan, `accelerate` (rewrite,
        fusion, source upload), `collect` (run, readback).  Returns the
        answer and the three clock readings around the two calls."""
        def sub(plan):
            return self._collect(self._accelerate(plan, self.conf),
                                 self.conf)
        t0 = time.perf_counter()
        with annotate("bench:accelerate"):
            plan = self._accelerate(
                self._builders[query](self.sources, sub), self.conf)
        t1 = time.perf_counter()
        with annotate("bench:collect"):
            answer = self._collect(plan, self.conf)
        return answer, (t0, t1, time.perf_counter())
