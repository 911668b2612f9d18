"""Operator metrics (reference `GpuExec.scala:27-56` GpuMetricNames +
Spark SQLMetrics): numOutputRows/numOutputBatches/totalTime plus per-op
extras, surfaced by `TpuExec.metrics`."""
from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"
TOTAL_TIME = "totalTime"
PEAK_DEVICE_MEMORY = "peakDevMemory"
BUFFER_TIME = "bufferTime"
DECODE_TIME = "tpuDecodeTime"
COMPILE_TIME = "compileTime"
# OOM retry harness (reference GpuMetric.NUM_RETRIES/NUM_SPLIT_RETRIES/
# RETRY_BLOCK_TIME on RmmRapidsRetryIterator): memory/retry.py charges
# these to the exec whose materialization hit pressure
NUM_RETRIES = "numRetries"
NUM_SPLIT_RETRIES = "numSplitRetries"
NUM_OOM_FALLBACKS = "numOomFallbacks"
SPILL_BYTES = "spillBytes"
RETRY_BLOCK_TIME = "retryBlockTime"
# out-of-core lane (memory/oocore.py): spillRunBytes is the serialized
# bytes an exec pushed through the spill tiers as sorted-run / grace
# partition / partial-agg state, numExternalMergePasses counts windowed
# merge/re-merge rounds, numGracePartitions the hash-partition fan-outs
# (summed across recursion depths), numSpillCorruptionsRecovered the
# corrupt spill re-reads that recovered from a replica or recompute
# instead of failing the query
SPILL_RUN_BYTES = "spillRunBytes"
NUM_EXTERNAL_MERGE_PASSES = "numExternalMergePasses"
NUM_GRACE_PARTITIONS = "numGracePartitions"
NUM_SPILL_CORRUPTIONS_RECOVERED = "numSpillCorruptionsRecovered"
# async pipeline layer (exec/pipeline.py PrefetchIterator): hostSyncs is
# the number of blocking device->host readbacks charged to an exec,
# pipelineWaitTime the ns a consumer spent blocked on an empty prefetch
# queue, prefetchHits the batches that were already buffered when the
# consumer asked (overlap actually won), prefetchStalls the gets that
# had to wait on the producer
HOST_SYNCS = "hostSyncs"
PIPELINE_WAIT_TIME = "pipelineWaitTime"
PREFETCH_HITS = "prefetchHits"
PREFETCH_STALLS = "prefetchStalls"
# shuffle fault recovery (shuffle/recovery.py): fetch failures seen at
# the reduce side, lost map tasks recomputed from lineage, bounded
# reduce retries, peers newly blacklisted, and ns spent inside recovery
# (invalidate + recompute), charged to the owning exchange
# query watchdog (utils/watchdog.py): deadline expirations declared,
# CancelTokens fired, diagnostic dumps emitted, and the widest observed
# gap between any heartbeat's beats (ms) — charged to the collected plan
# root when a query trips the watchdog
NUM_WATCHDOG_TIMEOUTS = "numWatchdogTimeouts"
NUM_CANCELS = "numCancels"
WATCHDOG_DUMPS = "watchdogDumps"
SLOWEST_HEARTBEAT = "slowestHeartbeatMs"
# whole-stage fusion (plan/fusion.py): a fused stage whose kernel
# failed to build/trace and fell back to the per-operator lane
NUM_FUSION_DEOPTS = "numFusionDeopts"
# SPMD whole-stage lane (exec/spmd.py): whole-mesh gang dispatches of
# a fused stage (one per stage regardless of partition count) and
# gangs that deopted back to the per-partition lane
NUM_SPMD_DISPATCHES = "numSpmdDispatches"
NUM_SPMD_DEOPTS = "numSpmdDeopts"
# the grouped aggregate's few-groups body (exec/aggregate.py): batches
# the update / merge kernel was handed with that body built in, and how
# many of them it took (a device count: the batch decides, on the
# device, by how many groups it has)
NUM_FEW_GROUPS_OFFERED = "numFewGroupsOffered"
NUM_FEW_GROUP_BATCHES = "numFewGroupBatches"
# HBM residency ledger (utils/residency.py): tracked buffers still
# attributed to a query when it finished — charged to the collected
# plan root by the end-of-query leak check
NUM_RESIDENCY_LEAKS = "numResidencyLeaks"
NUM_FETCH_FAILURES = "numFetchFailures"
NUM_MAP_RECOMPUTES = "numMapRecomputes"
NUM_STAGE_RETRIES = "numStageRetries"
NUM_PEERS_BLACKLISTED = "numPeersBlacklisted"
RECOVERY_TIME = "recoveryTime"
# tail tolerance (exec/speculation.py + shuffle hedging/replication):
# duplicate attempts launched for slow tasks and how many of them beat
# the original; hedged block fetches issued to replica peers and how
# many completed first; bytes pushed to backup executors at map-output
# write time; dead-peer map outputs recovered by promoting a live
# replica (no recompute); wire payloads whose CRC check caught
# in-flight damage (the retry path used to be invisible in
# EXPLAIN-with-metrics)
NUM_SPECULATIVE_TASKS = "numSpeculativeTasks"
NUM_SPECULATIVE_WINS = "numSpeculativeWins"
NUM_HEDGED_FETCHES = "numHedgedFetches"
NUM_HEDGED_WINS = "numHedgedWins"
REPLICATED_BYTES = "replicatedBytes"
NUM_REPLICA_PROMOTIONS = "numReplicaPromotions"
NUM_WIRE_CORRUPTIONS = "numWireCorruptions"
# data-movement ledger (utils/movement.py) per-node attribution:
# host->device bytes a scan uploaded, ICI collective payload bytes a
# mesh exchange moved, and the compressed/uncompressed wire bytes a
# manager-lane exchange's reducers pulled (compression ratio =
# compressed / uncompressed; shuffle/compression.py codec choice)
UPLOAD_BYTES = "uploadBytes"
COLLECTIVE_BYTES = "collectiveBytes"
SHUFFLE_COMPRESSED_BYTES = "shuffleCompressedBytes"
SHUFFLE_RAW_BYTES = "shuffleUncompressedBytes"


class MetricSet:
    """Counters that accept LAZY (device-scalar) values: a metric add of
    a not-yet-materialized row count must not force a ~150ms device sync
    in the hot path, so lazy values queue and resolve only when a metric
    is actually read (test assertions / UI display)."""

    def __init__(self):
        self._values = defaultdict(float)
        #: queued (name, value, op) updates; op is "add" or "max".
        #: BOTH ops queue lazily — set_max used to force a full
        #: _resolve() (a device readback wave) on every call, which put
        #: a host sync on the hot path of any exec that tracked a peak
        self._pending: list = []

    def add(self, name: str, value) -> None:
        if isinstance(value, (int, float)):
            self._values[name] += value
        else:
            self._pending.append((name, value, "add"))

    def set_max(self, name: str, value) -> None:
        """Raise `name` to at least `value`.  Queues like `add` — host
        values apply cheaply at resolve time, device scalars ride the
        same stacked readback wave — so a hot-path peak tracker never
        forces a device sync."""
        self._pending.append((name, value, "max"))

    def _resolve(self) -> None:
        if not self._pending:
            return
        import numpy as np
        from spark_rapids_tpu.utils import checks as CK
        pending, self._pending = self._pending, []
        # ONE stacked readback per dtype group for the whole pending
        # wave: per-value np.asarray costs a device round trip each, and
        # a long-running exec can queue hundreds of lazy row counts
        # between reads.  Grouping by dtype (instead of upcasting to one
        # stack dtype) keeps i32 row counts exact on non-x64 platforms;
        # by device too, since under a mesh an exec's partitions count
        # their rows a chip each and a stack takes one device's arrays.
        # Host values (ints/floats, common for set_max) resolve with no
        # readback at all.
        import jax.numpy as jnp
        resolved: list = [None] * len(pending)
        groups: dict = {}
        for i, (name, v, op) in enumerate(pending):
            if isinstance(v, (int, float)):
                resolved[i] = float(v)
                continue
            try:
                a = jnp.asarray(v).reshape(())
                groups.setdefault((str(a.dtype), frozenset(a.devices())),
                                  []).append((i, a))
            except Exception:
                resolved[i] = float(np.asarray(v))
        for items in groups.values():
            try:
                CK.note_host_sync("metrics.resolve",
                                  nbytes=8 * len(items))
                vals = np.asarray(jnp.stack([a for _, a in items]))
                for (i, _), val in zip(items, vals):
                    resolved[i] = float(val)
            except Exception:
                # mixed devices (sharded runs): per-value readback
                for i, a in items:
                    CK.note_host_sync("metrics.resolve", nbytes=8)
                    resolved[i] = float(np.asarray(a))
        # apply in FIFO order so interleaved add/max sequences see the
        # same values they would have seen resolving eagerly
        for (name, _, op), val in zip(pending, resolved):
            if op == "max":
                self._values[name] = max(self._values[name], val)
            else:
                self._values[name] += val

    def value(self, name: str) -> float:
        self._resolve()
        return self._values[name]

    @contextmanager
    def timed(self, name: str = TOTAL_TIME):
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.add(name, time.perf_counter_ns() - t0)

    def as_dict(self) -> dict:
        self._resolve()
        return dict(self._values)

    def __repr__(self):
        return f"MetricSet({self.as_dict()})"
