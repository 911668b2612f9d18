"""Shuffle exchange (reference `GpuShuffleExchangeExec.scala` +
`ShuffledBatchRDD.scala`).

The local-mode exchange: every upstream partition's batches are split with
the bound partitioner (device-side murmur3 + stable reorder + slice), and
each downstream partition concatenates its slices.  This is the analog of
the reference's default path (GPU partition -> serializer -> Spark netty
shuffle -> deserialize); the accelerated multi-chip path lives in
`parallel/collective_exchange.py` (ICI all-to-all under shard_map), and
`shuffle/transport.py` defines the pluggable cross-host transport SPI.

Also here: BroadcastExchangeExec (reference GpuBroadcastExchangeExec) —
collects the build side once and hands the same batch to every consumer.
"""
from __future__ import annotations

import threading
from typing import Iterator, Optional

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, concat_batches, programs_of, rows_made_known)
from spark_rapids_tpu.exec.base import TpuExec, UnaryExecBase
from spark_rapids_tpu.shuffle.partitioning import (
    RangePartitioning, TpuPartitioning)
from spark_rapids_tpu.utils import metrics as M
from spark_rapids_tpu.utils import profile as P


class ShuffleExchangeExec(UnaryExecBase):
    def __init__(self, partitioning: TpuPartitioning, child: TpuExec,
                 coalesce_small: bool = False):
        super().__init__(child)
        self._schema = child.output_schema()
        self.partitioning = partitioning.bind(self._schema)
        #: planner-set: the consumer only needs key CLUSTERING (e.g. a
        #: final aggregation), not index-aligned co-partitioning with a
        #: sibling exchange, so a small input may skip the split kernels
        #: entirely and land in one partition (AQE-style coalescing;
        #: reference analog: AQE coalesced shuffle reader,
        #: GpuCustomShuffleReaderExec).  NEVER set for join inputs.
        self.coalesce_small = coalesce_small

    def output_schema(self) -> T.Schema:
        return self._schema

    def output_partition_count(self) -> int:
        return self.partitioning.num_partitions

    def describe(self):
        return (f"ShuffleExchangeExec({type(self.partitioning).__name__}, "
                f"n={self.partitioning.num_partitions})")

    #: below this many input rows a range exchange degenerates to a
    #: single partition: a one-partition local sort is already globally
    #: ordered, and skipping bounds sampling + the split kernel saves
    #: several device round trips (AQE-style small-input coalescing)
    SMALL_RANGE_INPUT_ROWS = 1 << 15

    #: a coalesce_small exchange whose total input CAPACITY (static —
    #: no sync needed, unlike lazy row counts) stays at or below this
    #: emits one partition and skips the split kernels: dozens of tiny
    #: slice/concat dispatches cost more than
    #: single-partition consumption of a few thousand rows
    SMALL_COALESCE_INPUT_CAP = 1 << 16

    #: max map-side batches whose split outputs may be device-resident
    #: at once in the two-phase split pipeline (see _materialize); deep
    #: enough that count readbacks fully overlap, shallow enough that an
    #: arbitrarily large map side can't OOM the device
    SPLIT_PIPELINE_DEPTH = 8

    def _child_partitions(self) -> list:
        """The child's partitions for the lanes that split and
        concatenate on ONE device (local, manager, broadcast).  Under an
        active mesh with the child one partition a chip, every batch
        comes to one chip first, in one counted move (the map side then
        waits for all of itself); anywhere else the iterators as they
        are."""
        from spark_rapids_tpu.parallel import mesh as PM
        return PM.one_chip_partitions(self.child.execute_partitions(),
                                      "exchange-map")

    def _range_inputs(self):
        """Range partitioning needs two passes over the child (sample
        bounds, then split), so its inputs are materialized once here.
        Returns (inputs, small) — `small` means a one-partition exchange
        suffices.  Hash/round-robin callers must NOT use this: they
        stream batch-at-a-time so pre-split inputs are freed as they go."""
        inputs = [b.dense() for it in self._child_partitions()
                  for b in it if b.maybe_nonempty()]
        inputs = [b for b in inputs if b.num_rows > 0]
        total = sum(b.num_rows for b in inputs)
        n = self.partitioning.num_partitions
        small = total <= self.SMALL_RANGE_INPUT_ROWS or n == 1
        if not small and self.partitioning.bounds is None:
            self.partitioning.bounds = self._sample_bounds(
                self.partitioning, inputs)
        return inputs, small

    def _map_input_iter(self):
        """Map-side input stream (hash/round-robin lanes): child batches
        across all partitions, prefetched so the child's compute runs
        ahead of the split kernels (map side of the exchange pipeline
        break)."""
        from spark_rapids_tpu.exec.pipeline import maybe_prefetch
        return maybe_prefetch(
            (b for it in self._child_partitions()
             for b in it if b.maybe_nonempty()),
            label="exchange-map", metrics=self.metrics)

    def _materialize(self) -> list[list[ColumnarBatch]]:
        """Run the map side: split every input batch; bucket by target."""
        buckets: list[list[ColumnarBatch]] = [
            [] for _ in range(self.partitioning.num_partitions)]
        for p, s in self._split_slices():
            buckets[p].append(s)
        return buckets

    def _split_slices(self):
        """Map side as an incremental stream of (partition, slice)
        pairs: each input batch's split lands as soon as its count
        readback does, so a downstream consumer (AQE's streaming stage
        materialization) can overlap reduce-side work with the rest of
        the map side instead of waiting for every bucket."""
        part = self.partitioning
        n = part.num_partitions
        if isinstance(part, RangePartitioning):
            inputs, small = self._range_inputs()
            if small:
                for b in inputs:
                    yield 0, b
                return
            batch_iter = iter(inputs)
        else:
            batch_iter = self._map_input_iter()
            if self.coalesce_small and n > 1:
                with self.metrics.timed(M.TOTAL_TIME):
                    head, cap_seen = [], 0
                    exhausted = True
                    for b in batch_iter:
                        head.append(b)
                        cap_seen += b.capacity
                        if cap_seen > self.SMALL_COALESCE_INPUT_CAP:
                            exhausted = False
                            break
                if exhausted:
                    for b in head:
                        self.metrics.add("dataSize", b.device_size_bytes())
                        yield 0, b
                    return
                import itertools
                batch_iter = itertools.chain(head, batch_iter)
        if hasattr(part, "split_device"):
            # two-phase pipeline: queue split kernels back-to-back and
            # overlap the count readbacks, finishing the oldest batch
            # once SPLIT_PIPELINE_DEPTH are in flight.  By the time a
            # batch becomes the oldest its async count readback has
            # landed, so the whole map side still pays ~one effective
            # host round trip — but peak device memory is bounded at
            # SPLIT_PIPELINE_DEPTH full-capacity split outputs instead
            # of the entire map side.
            pending: list = []

            def finish_oldest():
                c, k, b = pending.pop(0)
                return part.finish_split(c, k, b)

            for batch in batch_iter:
                # constant label: the profiled span costs one global
                # read + a shared null context when profiling is off
                with self.metrics.timed(M.TOTAL_TIME), \
                        P.span("exchange-split", cat=P.CAT_SHUFFLE):
                    t = part.split_device(batch)
                    try:
                        t[1].copy_to_host_async()
                    except Exception:
                        pass
                    pending.append(t)
                    slices = (finish_oldest()
                              if len(pending) >= self.SPLIT_PIPELINE_DEPTH
                              else None)
                if slices is not None:
                    yield from self._emit_slices(slices)
            while pending:
                with self.metrics.timed(M.TOTAL_TIME), \
                        P.span("exchange-split", cat=P.CAT_SHUFFLE):
                    slices = finish_oldest()
                yield from self._emit_slices(slices)
        else:
            for batch in batch_iter:
                with self.metrics.timed(M.TOTAL_TIME), \
                        P.span("exchange-split", cat=P.CAT_SHUFFLE):
                    slices = part.partition_batch(batch)
                yield from self._emit_slices(slices)

    def _emit_slices(self, slices):
        for p, s in enumerate(slices):
            if s is not None and s.maybe_nonempty():
                self.metrics.add("dataSize", s.device_size_bytes())
                yield p, s

    def _sample_bounds(self, part: RangePartitioning, inputs):
        """Driver-side reservoir sampling for range bounds (reference
        GpuRangePartitioner.sketch/SamplingUtils)."""
        import numpy as np
        import jax.numpy as jnp
        from spark_rapids_tpu.columnar.vector import bucket_capacity
        samples = []
        sample_rows = 0
        target = 20 * part.num_partitions
        for batch in inputs:
            # evenly-spaced sample of each batch (the reference uses
            # reservoir sampling; deterministic striding is equivalent
            # for bound estimation and cheaper on device)
            take = min(batch.num_rows, max(2, target))
            idx = np.linspace(0, batch.num_rows - 1, take).astype(int)
            cap = bucket_capacity(take)
            sel = jnp.asarray(np.pad(idx, (0, cap - take)))
            valid = jnp.arange(cap) < take
            samples.append(batch.gather(sel, valid, take))
            sample_rows += take
            if sample_rows >= 4 * target:
                break
        if not samples:
            from spark_rapids_tpu.columnar.batch import empty_batch
            return empty_batch(self._schema)
        sample = concat_batches(samples)
        return RangePartitioning.compute_bounds(
            sample, part.order, part.num_partitions)

    def execute_partitions(self):
        from spark_rapids_tpu import config as C
        mesh_axis = self._mesh_routable()
        if mesh_axis is not None:
            return self._execute_via_mesh(*mesh_axis)
        if C.get_active_conf()[C.RAPIDS_SHUFFLE_ENABLED]:
            return self._execute_via_manager()
        from spark_rapids_tpu.exec.pipeline import maybe_prefetch
        with P.span(P.SPAN_EXCHANGE_WRITE) as sp:
            buckets = self._materialize()
            if sp is not None:
                slices = [s for bs in buckets for s in bs]
                sp.args = {
                    "partitions": len(buckets), "slices": len(slices),
                    "rows": P.known_rows(slices),
                    "capacity_rows": sum(s.capacity for s in slices),
                    "bytes": sum(s.device_size_bytes() for s in slices)}
        # reduce side of the exchange pipeline break: each partition's
        # merge/consolidation dispatches run ahead of its consumer
        return [maybe_prefetch(self._merged_reader(bs),
                               label="exchange-reduce",
                               metrics=self.metrics)
                for bs in buckets]

    #: reduce-side consolidation target (the role GpuCoalesceBatches
    #: plays after GPU shuffles, `GpuCoalesceBatches.scala:53`): a
    #: partition's split slices merge device-side up to this capacity
    #: before flowing downstream.  Without it every map-side batch
    #: contributes one slice per partition PER HOP, so a deep
    #: exchange chain multiplies batch count exponentially — TPC-DS
    #: q64 (19 exchanges) reached tens of thousands of live 1K-cap
    #: batches and tens of GB of device arrays.
    MERGE_TARGET_CAP = 1 << 16

    def _merged_reader(self, bs: list[ColumnarBatch]):
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.columnar.vector import bucket_capacity
        # scale the consolidation target with the session's batch-row
        # budget: a 26M-row reduce partition under the 64K floor came
        # out as ~400 tiny batches — 400 probe/agg dispatches downstream
        target_cap = max(self.MERGE_TARGET_CAP, bucket_capacity(
            int(C.get_active_conf()[C.MAX_BATCH_ROWS])))
        group: list[ColumnarBatch] = []
        cap_sum = 0
        ph = P.phase(P.SPAN_EXCHANGE_READ, slices=len(bs), batches=0,
                     rows=0, capacity_rows=0)

        def done(m: ColumnarBatch) -> ColumnarBatch:
            if ph is not None:
                ph.add(batches=1, rows=P.known_rows([m]),
                       capacity_rows=m.capacity)
            return m

        @programs_of("exchange")    # the consolidation's device programs
        def flush():
            if len(group) == 1:
                m = group[0]
            elif self.coalesce_small:
                # consumer is a final aggregation / window that compacts
                # its groups right away, so the lazy concat's worst-case
                # capacity (bounded by MERGE_TARGET_CAP per flush group)
                # never propagates — and skipping the count sync keeps
                # the whole collect down to ONE readback wave (the
                # count sync below must WAIT for every queued
                # partial-agg kernel before reading)
                m = concat_batches(list(group))
            else:
                # sync the slices' row counts (ONE stacked readback)
                # and concat TIGHT: the sync-free lazy concat keeps
                # the summed worst-case capacity, and across a deep
                # exchange chain that re-inflates every hop to the
                # merge target no matter how few real rows flow
                dense = [b.dense() for b in group]
                rows_made_known(dense, "exchange.merge")
                m = concat_batches([b for b in dense if b.num_rows > 0]
                                   or dense[:1])
            self.metrics.add(M.NUM_OUTPUT_ROWS, m._rows)
            self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
            return m

        try:
            for b in bs:
                if group and cap_sum + b.capacity > target_cap:
                    yield done(flush())
                    group, cap_sum = [], 0
                group.append(b)
                cap_sum += b.capacity
            if group:
                yield done(flush())
        finally:
            if ph is not None:
                ph.close()

    def _mesh_routable(self):
        """The accelerated ICI lane applies when: the conf enables it, a
        device mesh is active, the partitioning is murmur3 hash over plain
        bound columns, and the partition count equals the mesh size (so
        device d IS partition d).  Anything else falls back to the
        local/manager lane — mirroring the reference, whose UCX data plane
        only takes over when the rapids shuffle manager is installed
        (RapidsShuffleInternalManager.scala:199)."""
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.exprs.base import BoundReference
        from spark_rapids_tpu.parallel import mesh as PM
        from spark_rapids_tpu.shuffle.partitioning import HashPartitioning
        if not C.get_active_conf()[C.MESH_EXCHANGE_ENABLED]:
            return None
        active = PM.get_active_mesh()
        if active is None:
            return None
        mesh, axis = active
        part = self.partitioning
        if not isinstance(part, HashPartitioning):
            return None
        if part.num_partitions != mesh.shape[axis]:
            return None
        if not all(isinstance(e, BoundReference) for e in part.exprs):
            return None
        return mesh, axis

    #: test-facing counter (ExecutionPlanCapture discipline): number of
    #: exchanges actually routed through the mesh collective lane
    _MESH_EXCHANGES_RUN = 0
    #: oversized single batches sharded across the mesh (SURVEY §5)
    _OVERSIZED_SPLITS = 0
    #: per mesh exchange run, the sorted device ids that held a shard of
    #: its output (`addressable_shards`): code that has only met virtual
    #: CPU devices may put everything on the first device, and
    #: `chip_smoke.py --chips 4` asserts from this that it did not
    _MESH_SHARD_DEVICES: list = []

    def _execute_via_mesh(self, mesh, axis):
        """Accelerated path: one SPMD all-to-all over the mesh replaces
        the per-batch split + bucket copy of the local lane.  Each mesh
        device owns one output partition; received rows are compacted
        device-side into a batch sized from the count phase's totals.

        One partition a chip, in and out: a child with one partition a
        chip is stacked where it lies (chip d's rows made one batch on
        chip d, padded to the common capacity by one program there, and
        the global operand assembled from those single-device arrays),
        and output partition d IS chip d's addressable shard of the
        result, never an index into the global array: a row indexed out
        of the sharded stack stays spread over the mesh, and the first
        Mosaic kernel to receive one refuses it (four v5e chips, PR 25).
        So the only bytes that cross chips are the all-to-all's.  Any
        other child (another partition count, batches committed nowhere)
        is dealt round-robin and spread through the counted move."""
        import jax
        import numpy as np
        from spark_rapids_tpu.columnar.batch import empty_batch
        from spark_rapids_tpu.columnar.vector import (
            ColumnVector, bucket_capacity)
        from spark_rapids_tpu.exec.pipeline import drain_partitions
        from spark_rapids_tpu.parallel import mesh as PM
        from spark_rapids_tpu.parallel.collective_exchange import (
            build_all_to_all_exchange, build_count_exchange,
            local_stack, local_unstack, stacked_payload_bytes,
            watched_collective)
        n = self.partitioning.num_partitions
        chips = list(mesh.devices.flat)
        from spark_rapids_tpu import config as C
        max_rows = C.get_active_conf()[C.MAX_BATCH_ROWS]
        groups: list[list[ColumnarBatch]] = [[] for _ in range(n)]
        with P.span(P.SPAN_EXCHANGE_WRITE) as write:
            parts = drain_partitions(self.child.execute_partitions(),
                                     label="exchange-map",
                                     metrics=self.metrics)
            aligned = len(parts) == n
            slot = 0
            for p, part in enumerate(parts):
                for b in part:
                    if not b.maybe_nonempty():
                        continue
                    # size LAZY batches by CAPACITY (a safe upper bound
                    # on rows): coalesce's lazy_bounded pass-through
                    # emits batches up to LAZY_PASS_MULT x the row cap
                    # whole, and those must not skip HBM-budget sharding
                    # and land entire on one chip.  Only the must-shard
                    # shape pays the count sync (b.num_rows below).
                    est_rows = (b.num_rows if b.num_rows_known
                                else b.capacity)
                    if est_rows > max_rows and b.num_rows > max_rows:
                        # SURVEY §5 long-context analog: ONE batch larger
                        # than the per-chip budget is sharded ACROSS the
                        # mesh before the all-to-all (the sp lane),
                        # instead of overflowing one chip's HBM
                        # (reference guard: GpuCoalesceBatches.scala:
                        # 166-169 + spill tiers)
                        per = -(-b.num_rows // n)
                        ShuffleExchangeExec._OVERSIZED_SPLITS += 1
                        for lo in range(0, b.num_rows, per):
                            groups[slot % n].append(
                                b.slice(lo, min(per, b.num_rows - lo)))
                            slot += 1
                    elif aligned:
                        groups[p].append(b)
                    else:
                        groups[slot % n].append(b)
                        slot += 1
            def local_of(d: int):
                """Chip d's rows as one dense batch on chip d."""
                g = groups[d]
                if not g:
                    with jax.default_device(chips[d]):
                        g = [empty_batch(self._schema)]
                g = PM.to_one_chip(g, "exchange-spread", device=chips[d],
                                   strict=True)
                with programs_of("exchange"):
                    yield concat_batches(g).dense()
            # a chip each, side by side: on a cold cache the chips'
            # copies of the compaction and the concat compile together
            locals_ = [b for (b,) in drain_partitions(
                [local_of(d) for d in range(n)], label="exchange-stack",
                metrics=self.metrics)]
            cap = max(b.capacity for b in locals_)
            char_caps = tuple(
                max(b.columns[i].char_cap for b in locals_)
                if f.dtype.is_string else 0
                for i, f in enumerate(self._schema.fields))
            # chip d's block of the stacked operand, made on chip d
            sharding = PM.data_sharding(mesh, axis)
            blocks = [local_stack(b.columns, b.num_rows_i32, cap=cap,
                                  char_caps=char_caps) for b in locals_]
            arrs, num_rows = jax.tree_util.tree_map(
                lambda *xs: jax.make_array_from_single_device_arrays(
                    (n,) + xs[0].shape[1:], sharding, list(xs)), *blocks)
            payload = stacked_payload_bytes(arrs)
            if write is not None:
                write.args = {
                    "partitions": n, "rows": P.known_rows(locals_),
                    "capacity_rows": n * cap, "bytes": payload}
        key_idx = tuple(e.ordinal for e in self.partitioning.exprs)
        # process-global LRU (bounded + clearable): mesh identity enters
        # the key as device ids, not the Mesh object, so dead meshes are
        # not pinned beyond the cached executable's LRU lifetime
        from spark_rapids_tpu.exec.base import KernelCache
        cache = KernelCache((
            "mesh_exchange", axis,
            tuple(d.id for d in chips),
            tuple((f.name, str(f.dtype)) for f in self._schema.fields),
            key_idx))
        schema = self._schema
        ShuffleExchangeExec._MESH_EXCHANGES_RUN += 1
        # the whole-mesh dispatch gate covers every enqueue of a
        # whole-mesh program (count phase, data phase): concurrent
        # whole-mesh programs enqueued from two threads can invert
        # per-device queue order and deadlock the collective rendezvous
        # (exec/scheduler.py)
        from spark_rapids_tpu.exec import scheduler as S
        with self.metrics.timed(M.TOTAL_TIME), \
                P.span(P.SPAN_EXCHANGE_COLLECTIVE, chips=n,
                       payload_bytes=payload,
                       cross_chip_bytes=payload - payload // n) as coll, \
                S.whole_mesh_dispatch(label="mesh-exchange"):
            # movement ledger: the payload the data-phase all-to-all
            # ships over ICI — every column's stacked data + validity
            # (+ lengths) arrays (the count phase is n_dev ints, noise)
            from spark_rapids_tpu.utils import movement as MV
            if MV.ledger() is not None:
                self.metrics.add(M.COLLECTIVE_BYTES, payload)
            # two-phase exchange (ADVICE r2): a counts-only all-to-all
            # sizes the data phase's receive buffers from ACTUAL totals
            # — the old n_dev*cap worst case OOMs HBM-scale batches
            count_fn = cache.get_or_build(
                ("count", cap),
                lambda: build_count_exchange(mesh, axis, schema,
                                             key_idx, cap))
            from spark_rapids_tpu.utils import checks as CK
            CK.note_host_sync("exchange.mesh", nbytes=4 * n)
            totals = watched_collective(
                lambda: np.asarray(count_fn(arrs, num_rows)),
                label="mesh-count")
            out_cap = int(bucket_capacity(max(int(totals.max()), 1)))
            if coll is not None:
                coll.args["out_cap"] = out_cap
            step = cache.get_or_build(
                ("step", cap, out_cap),
                lambda: build_all_to_all_exchange(
                    mesh, axis, schema, key_idx, cap,
                    out_capacity=out_cap))
            out_arrs, out_rows = watched_collective(
                lambda: step(arrs, num_rows), label="mesh-exchange",
                nbytes=payload if MV.ledger() is not None else 0)
            ShuffleExchangeExec._MESH_SHARD_DEVICES.append(sorted(
                s.device.id for s in out_rows.addressable_shards))
        del out_rows    # the count phase's totals ARE the rows received

        def reader(d: int):
            # partition d is chip d's own shard of every output array: a
            # single-device array that is already there
            rows = int(totals[d])
            if rows == 0:
                return
            ph = P.phase(P.SPAN_EXCHANGE_READ, rows=rows,
                         capacity_rows=out_cap, device=chips[d].id)
            try:
                mine = jax.tree_util.tree_map(
                    lambda a: next(s.data for s in a.addressable_shards
                                   if s.device == chips[d]), out_arrs)
                b = ColumnarBatch(schema, [
                    ColumnVector(f.dtype, *col) for f, col in zip(
                        schema.fields, local_unstack(mine))], rows)
                self.metrics.add("dataSize", b.device_size_bytes())
                self.metrics.add(M.NUM_OUTPUT_ROWS, rows)
                self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
                yield b
            finally:
                if ph is not None:
                    ph.close()
        return [reader(d) for d in range(n)]

    _SHUFFLE_IDS = iter(range(1, 1 << 31))

    def _execute_via_manager(self):
        """Accelerated path: map outputs land in the spillable shuffle
        catalog; reducers pull through the caching reader (reference
        RapidsShuffleManager write/read, SURVEY.md §3.4).

        Fault recovery (shuffle/recovery.py): map tasks spread across
        spark.rapids.shuffle.localExecutors in-process executors
        (round-robin over NON-blacklisted peers); the reduce side runs
        through a ShuffleRecoveryDriver whose recompute closure retains
        this exchange's map lineage — a lost peer's map tasks re-run
        from `self.child` and land on the (always-alive) reducing
        executor."""
        from spark_rapids_tpu import config as C
        from spark_rapids_tpu.shuffle.manager import (
            MapOutputRegistry, TpuShuffleManager)
        from spark_rapids_tpu.shuffle.recovery import (
            PeerHealth, ShuffleRecoveryDriver)
        conf = C.get_active_conf()
        n_execs = max(1, int(conf[C.SHUFFLE_LOCAL_EXECUTORS]))
        names = (["local"] if n_execs == 1
                 else [f"local-{i}" for i in range(n_execs)])
        mgrs = [TpuShuffleManager.get_or_create(nm) for nm in names]
        primary = mgrs[0]
        health = PeerHealth.get()
        shuffle_id = next(ShuffleExchangeExec._SHUFFLE_IDS)
        for m in mgrs:
            m.register_shuffle(shuffle_id)
        part = self.partitioning
        if isinstance(part, RangePartitioning) and part.bounds is None:
            # two passes needed: materialize per-map batches once so the
            # bounds sample and the split see the same data
            per_map = [[b for b in it if b.num_rows > 0]
                       for it in self._child_partitions()]
            part.bounds = self._sample_bounds(
                part, [b for bs in per_map for b in bs])
            map_iters = [iter(bs) for bs in per_map]
        else:
            from spark_rapids_tpu.exec.pipeline import maybe_prefetch
            map_iters = [maybe_prefetch(it, label="exchange-map",
                                        metrics=self.metrics)
                         for it in self._child_partitions()]
        n = part.num_partitions
        repl_factor = max(1, int(conf[C.SHUFFLE_REPLICATION_FACTOR]))

        def healthy_mgrs():
            ok = [m for m in mgrs
                  if not any(health.is_blacklisted(a) for a in
                             (m.loop_address, m.tcp_address) if a)]
            return ok or [primary]

        def replicas_for(mgr):
            """factor-1 backup executors for a map task hosted on
            `mgr`: the next healthy peers in ring order."""
            if repl_factor < 2:
                return ()
            pool_ = [m for m in healthy_mgrs() if m is not mgr]
            return tuple(pool_[:repl_factor - 1])

        def write_map_task(map_id, batch_iter, mgr, epoch=None,
                           first_wins=False):
            from spark_rapids_tpu.utils import watchdog as W
            writer = mgr.get_writer(shuffle_id, map_id,
                                    replicas=replicas_for(mgr))
            sp = P.span(f"shuffle-map:s{shuffle_id}m{map_id}",
                        cat=P.CAT_SHUFFLE) \
                if P.tracer() is not None else P._NULL_SPAN
            try:
                with sp:
                    for batch in batch_iter:
                        # batch boundary = cancellation point: a losing
                        # speculative attempt stops here, promptly
                        W.check_cancelled()
                        # seeded slow-task injection (the straggler
                        # model speculation must beat)
                        W.maybe_slow("map-task", conf=conf,
                                     executor_id=mgr.executor_id)
                        if batch.num_rows == 0:
                            continue
                        with self.metrics.timed(M.TOTAL_TIME):
                            slices = part.partition_batch(batch)
                        for p, s in enumerate(slices):
                            if s is not None and s.num_rows > 0:
                                writer.write_partition(p, s)
                                self.metrics.add("dataSize",
                                                 s.device_size_bytes())
            except BaseException:
                writer.abort()
                raise
            writer.commit(n, epoch=epoch, first_wins=first_wins)
            if writer.replicated_bytes:
                self.metrics.add(M.REPLICATED_BYTES,
                                 writer.replicated_bytes)

        def lineage(map_id):
            # retained map-side lineage (shared with recovery): a
            # FRESH run of exactly this child partition
            return self._child_partitions()[map_id]

        def backup_for(exclude_mgr):
            ok = [m for m in healthy_mgrs() if m is not exclude_mgr]
            return ok[0] if ok else None

        from spark_rapids_tpu.exec import speculation as SPEC
        spec = SPEC.maybe_create(
            shuffle_id, conf, self.metrics, write_map_task, lineage,
            backup_for, num_executors=len(mgrs))
        try:
            pool = healthy_mgrs()
            for map_id, it in enumerate(map_iters):
                mgr = pool[map_id % len(pool)]
                if spec is not None:
                    spec.run_task(map_id, it, mgr)
                else:
                    write_map_task(map_id, it, mgr)
            # arm the partial-read guard: a reduce over fewer outputs
            # than this must FetchFail, never return partial data
            MapOutputRegistry.set_expected_maps(shuffle_id,
                                                len(map_iters))
        except BaseException:
            # failed map stage: free completed tasks' buffers too — no
            # reader will ever run _done()
            for m in mgrs:
                m.unregister_shuffle(shuffle_id)
            raise
        finally:
            if spec is not None:
                spec.finish()

        driver = None
        if conf[C.SHUFFLE_RECOVERY_ENABLED]:
            def recompute(lost_map_ids, epoch):
                # retained map-side lineage: re-run ONLY the lost map
                # partitions of the child, splitting with the same
                # bound partitioning (range bounds already sampled),
                # and land them on the reducing executor — the one
                # peer recovery can rely on being alive
                its = self._child_partitions()
                for map_id in lost_map_ids:
                    write_map_task(map_id, its[map_id], primary,
                                   epoch=epoch)
            driver = ShuffleRecoveryDriver(
                primary, shuffle_id, recompute, conf=conf,
                metrics=self.metrics)

        # free the shuffle's spillable buffers + map-output entries once
        # every partition reader is exhausted (or closed early)
        remaining = [n]
        lock = threading.Lock()

        def _done():
            with lock:
                remaining[0] -= 1
                last = remaining[0] == 0
            if last:
                for m in mgrs:
                    m.unregister_shuffle(shuffle_id)

        def reader(p: int):
            try:
                batches = (driver.read_partition(p)
                           if driver is not None
                           else primary.get_reader(shuffle_id, p,
                                                   metrics=self.metrics))
                for b in batches:
                    self.metrics.add(M.NUM_OUTPUT_ROWS, b.num_rows)
                    self.metrics.add(M.NUM_OUTPUT_BATCHES, 1)
                    yield b
            finally:
                _done()
        from spark_rapids_tpu.exec.pipeline import maybe_prefetch
        return [maybe_prefetch(reader(p), label="exchange-reduce",
                               metrics=self.metrics)
                for p in range(n)]

    def execute_columnar(self):
        for it in self.execute_partitions():
            yield from it


class BroadcastTimeoutError(RuntimeError):
    """Build-side materialization exceeded spark.sql.broadcastTimeout
    (reference GpuBroadcastExchangeExec: 'Could not execute broadcast
    in N secs' from the collect future's timeout)."""


class BroadcastTooLargeError(RuntimeError):
    """Build side exceeded spark.rapids.tpu.maxBroadcastTableBytes
    (Spark's 8GB broadcast-table limit analog)."""


class BroadcastExchangeExec(UnaryExecBase):
    """Collect the (small) build side once; every consumer gets the same
    single batch (reference GpuBroadcastExchangeExec +
    SerializeConcatHostBuffersDeserializeBatch semantics, minus the
    torrent wire format).

    Guards (reference GpuBroadcastExchangeExec.scala:238): the build
    collect is bounded by spark.sql.broadcastTimeout and the total
    device bytes by spark.rapids.tpu.maxBroadcastTableBytes, so a
    runaway build side fails with a clear error instead of hanging the
    query or exhausting HBM.  Design shift: the reference runs the
    collect on a dedicated thread pool and times out the future; this
    engine executes one query at a time on the driver thread, so the
    timeout is COOPERATIVE — checked between build-side batches (a
    single wedged batch kernel is the driver's watchdog's job)."""

    def __init__(self, child: TpuExec):
        super().__init__(child)
        self._schema = child.output_schema()
        self._cached: Optional[ColumnarBatch] = None

    def output_schema(self):
        return self._schema

    def output_partition_count(self) -> int:
        return 1

    def broadcast_batch(self) -> ColumnarBatch:
        if self._cached is None:
            import time
            from spark_rapids_tpu import config as C
            conf = C.get_active_conf()
            timeout_s = conf[C.BROADCAST_TIMEOUT]
            max_bytes = conf[C.MAX_BROADCAST_TABLE_BYTES]
            with self.metrics.timed("broadcastTime"):
                t0 = time.monotonic()
                batches, total = [], 0
                from spark_rapids_tpu.parallel import mesh as PM
                for it in PM.one_chip_partitions(
                        self.child.execute_partitions(), "broadcast"):
                    for b in it:
                        if not b.maybe_nonempty():
                            continue
                        batches.append(b)
                        total += b.device_size_bytes()
                        if total > max_bytes:
                            raise BroadcastTooLargeError(
                                f"broadcast build side reached {total} "
                                f"bytes > spark.rapids.tpu."
                                f"maxBroadcastTableBytes={max_bytes}")
                        if time.monotonic() - t0 > timeout_s:
                            raise BroadcastTimeoutError(
                                f"could not execute broadcast in "
                                f"{timeout_s} secs "
                                f"(spark.sql.broadcastTimeout)")
                if batches:
                    self._cached = concat_batches(batches).dense()
                else:
                    from spark_rapids_tpu.columnar.batch import empty_batch
                    self._cached = empty_batch(self._schema)
                self.metrics.add("dataSize",
                                 self._cached.device_size_bytes())
        return self._cached

    def execute_columnar(self):
        yield self.broadcast_batch()

    def execute_partitions(self):
        return [self.execute_columnar()]
