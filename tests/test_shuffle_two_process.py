"""Two-PROCESS shuffle: a real second executor process fetches map
outputs over the TCP lane, address exchange via MapStatus — no shared
memory (one level more real than the reference's mocked-transport
suites, SURVEY.md §4 tier 2)."""
import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

CHILD = r"""
import json, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import spark_rapids_tpu
from spark_rapids_tpu import config as C
from spark_rapids_tpu.shuffle.manager import (MapOutputRegistry,
                                              MapStatus,
                                              TpuShuffleManager)

spec = json.loads(sys.stdin.read())
conf_map = {"spark.rapids.shuffle.enabled": True}
if spec.get("codec"):
    conf_map["spark.rapids.shuffle.compression.codec"] = spec["codec"]
with C.session(C.RapidsConf(conf_map)):
    mgr = TpuShuffleManager("executor-B")
    # MapStatus entries arrive over the wire (the MapOutputTracker role);
    # the loop:// address is unreachable from this process, so the
    # reader must fall back to the TCP address
    for m in spec["outputs"]:
        MapOutputRegistry.register(
            spec["shuffle_id"], m["map_id"],
            MapStatus(m["executor_id"], m["address"],
                      m["partition_sizes"], tcp_address=m["tcp_address"]))
    result = {}
    lo, hi = spec.get("partition_range",
                      [0, spec["num_partitions"]])
    timeout = spec.get("timeout", 30.0)
    try:
        for p in range(lo, hi):
            rows = 0
            ksum = 0
            for batch in mgr.get_reader(spec["shuffle_id"], p,
                                        timeout=timeout):
                df = batch.to_pandas()
                rows += len(df)
                ksum += int(df["k"].sum())
            result[str(p)] = {"rows": rows, "ksum": ksum}
    except Exception as e:
        if spec.get("expect_fetch_failed"):
            from spark_rapids_tpu.shuffle.client_server import \
                FetchFailedError
            kind = ("FETCH_FAILED"
                    if isinstance(e, FetchFailedError)
                    else type(e).__name__)
            print("RESULT:" + json.dumps({"error": kind}))
            print(kind)
            mgr.close()
            sys.exit(0)
        raise
    mgr.close()
print("RESULT:" + json.dumps(result))
"""


def test_cross_process_fetch_via_tcp():
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

    rng = np.random.default_rng(17)
    n_parts, shuffle_id = 3, 4242
    with C.session(C.RapidsConf({"spark.rapids.shuffle.enabled": True})):
        mgr = TpuShuffleManager("executor-A")
        mgr.register_shuffle(shuffle_id)
        expected = {p: {"rows": 0, "ksum": 0} for p in range(n_parts)}
        outputs = []
        for map_id in range(2):
            writer = mgr.get_writer(shuffle_id, map_id)
            for p in range(n_parts):
                k = rng.integers(0, 1000, 40 + 10 * p).astype(np.int64)
                batch = ColumnarBatch.from_pandas(pd.DataFrame({"k": k}))
                writer.write_partition(p, batch)
                expected[p]["rows"] += len(k)
                expected[p]["ksum"] += int(k.sum())
            status = writer.commit(n_parts)
            outputs.append({
                "map_id": map_id,
                "executor_id": status.executor_id,
                "address": status.address,
                "tcp_address": status.tcp_address,
                "partition_sizes": status.partition_sizes,
            })
        assert all(o["address"].startswith("loop://") for o in outputs)
        assert all(o["tcp_address"].startswith("tcp://") for o in outputs)

        spec = json.dumps({"shuffle_id": shuffle_id,
                           "num_partitions": n_parts,
                           "outputs": outputs})
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # child needs no virtual mesh
        proc = subprocess.run(
            [sys.executable, "-c", CHILD], input=spec.encode(),
            capture_output=True, timeout=240, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        out = proc.stdout.decode()
        assert proc.returncode == 0, \
            f"child failed:\n{out}\n{proc.stderr.decode()[-2000:]}"
        line = [ln for ln in out.splitlines()
                if ln.startswith("RESULT:")][-1]
        got = json.loads(line[len("RESULT:"):])
        for p in range(n_parts):
            assert got[str(p)] == expected[p], f"partition {p}"
        mgr.unregister_shuffle(shuffle_id)
        mgr.close()


def _write_maps(mgr, shuffle_id, n_parts, n_maps=2, rng_seed=17,
                conf_extra=None):
    """Shared map-side: returns (outputs spec list, expected totals)."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    rng = np.random.default_rng(rng_seed)
    expected = {p: {"rows": 0, "ksum": 0} for p in range(n_parts)}
    outputs = []
    for map_id in range(n_maps):
        writer = mgr.get_writer(shuffle_id, map_id)
        for p in range(n_parts):
            k = rng.integers(0, 1000, 40 + 10 * p).astype(np.int64)
            batch = ColumnarBatch.from_pandas(pd.DataFrame({"k": k}))
            writer.write_partition(p, batch)
            expected[p]["rows"] += len(k)
            expected[p]["ksum"] += int(k.sum())
        status = writer.commit(n_parts)
        outputs.append({
            "map_id": map_id,
            "executor_id": status.executor_id,
            "address": status.address,
            "tcp_address": status.tcp_address,
            "partition_sizes": status.partition_sizes,
        })
    return outputs, expected


def _spawn_reader(spec_dict):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-c", CHILD],
        input=json.dumps(spec_dict).encode(),
        capture_output=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _check_child(proc, expected, n_parts):
    out = proc.stdout.decode()
    assert proc.returncode == 0, \
        f"child failed:\n{out}\n{proc.stderr.decode()[-2000:]}"
    line = [ln for ln in out.splitlines()
            if ln.startswith("RESULT:")][-1]
    got = json.loads(line[len("RESULT:"):])
    for p in range(n_parts):
        assert got[str(p)] == expected[p], f"partition {p}"


def test_cross_process_fetch_compressed():
    """Remote fetch of lz4-framed (CRC-checked) compressed payloads —
    the reference's TableCompressionCodec path over a real wire."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    n_parts, shuffle_id = 3, 4243
    conf = C.RapidsConf({
        "spark.rapids.shuffle.enabled": True,
        "spark.rapids.shuffle.compression.codec": "lz4"})
    with C.session(conf):
        mgr = TpuShuffleManager("executor-A")
        mgr.register_shuffle(shuffle_id)
        outputs, expected = _write_maps(mgr, shuffle_id, n_parts,
                                        rng_seed=19)
        proc = _spawn_reader({"shuffle_id": shuffle_id,
                              "num_partitions": n_parts,
                              "outputs": outputs,
                              "codec": "lz4"})
        _check_child(proc, expected, n_parts)
        mgr.unregister_shuffle(shuffle_id)
        mgr.close()


def test_cross_process_fetch_spilled_tier():
    """The remote side fetches buffers that were spilled device->host
    (and partially ->disk) BEFORE the fetch: BufferSendState must pull
    from whatever tier holds the data (reference
    RapidsShuffleServer.scala:380 acquires from any tier)."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.memory.env import ResourceEnv
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    n_parts, shuffle_id = 3, 4244
    with C.session(C.RapidsConf({"spark.rapids.shuffle.enabled": True})):
        env = ResourceEnv.get()
        mgr = TpuShuffleManager("executor-A")
        mgr.register_shuffle(shuffle_id)
        outputs, expected = _write_maps(mgr, shuffle_id, n_parts,
                                        rng_seed=23)
        spilled = env.device_store.synchronous_spill(0)
        assert spilled > 0
        # push part of the host tier onward to disk too
        env.host_store.synchronous_spill(env.host_store.spillable_size
                                         // 2)
        proc = _spawn_reader({"shuffle_id": shuffle_id,
                              "num_partitions": n_parts,
                              "outputs": outputs})
        _check_child(proc, expected, n_parts)
        mgr.unregister_shuffle(shuffle_id)
        mgr.close()


def test_cross_process_two_concurrent_reducers():
    """Two reader PROCESSES fetch different partitions concurrently
    from one server (the reference's throttled multi-client serving)."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    n_parts, shuffle_id = 4, 4245
    with C.session(C.RapidsConf({"spark.rapids.shuffle.enabled": True})):
        mgr = TpuShuffleManager("executor-A")
        mgr.register_shuffle(shuffle_id)
        outputs, expected = _write_maps(mgr, shuffle_id, n_parts,
                                        rng_seed=29)
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        procs = []
        for lo, hi in ((0, 2), (2, 4)):
            spec = json.dumps({"shuffle_id": shuffle_id,
                               "num_partitions": n_parts,
                               "partition_range": [lo, hi],
                               "outputs": outputs})
            procs.append(subprocess.Popen(
                [sys.executable, "-c", CHILD],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, env=env, cwd=cwd))
            procs[-1].stdin.write(spec.encode())
            procs[-1].stdin.close()
        results = {}
        for proc, (lo, hi) in zip(procs, ((0, 2), (2, 4))):
            out = proc.stdout.read().decode()
            err = proc.stderr.read().decode()
            assert proc.wait(timeout=240) == 0, f"{out}\n{err[-2000:]}"
            line = [ln for ln in out.splitlines()
                    if ln.startswith("RESULT:")][-1]
            got = json.loads(line[len("RESULT:"):])
            for p in range(lo, hi):
                results[p] = got[str(p)]
        for p in range(n_parts):
            assert results[p] == expected[p], f"partition {p}"
        mgr.unregister_shuffle(shuffle_id)
        mgr.close()


CHILD_SERVER = r"""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import pandas as pd
import spark_rapids_tpu
from spark_rapids_tpu import config as C
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.shuffle.manager import TpuShuffleManager

# throttle the data plane so the parent's fetch is reliably IN FLIGHT
# when it kills this process (a fast local socket would otherwise race
# the kill): every DATA-sized frame pays a small sleep
import spark_rapids_tpu.shuffle.ici_transport as ici
_orig_send = ici._send_all
def _slow_send(conn, data):
    _orig_send(conn, data)
    if len(data) > 512:
        time.sleep(0.01)
ici._send_all = _slow_send

spec = json.loads(sys.stdin.readline())
conf = C.RapidsConf({
    "spark.rapids.shuffle.enabled": True,
    "spark.rapids.shuffle.bounceBuffers.size": spec["bounce"]})
with C.session(conf):
    mgr = TpuShuffleManager("executor-S")
    mgr.register_shuffle(spec["shuffle_id"])
    rng = np.random.default_rng(5)
    outputs = []
    for map_id, rows in enumerate(spec["map_rows"]):
        w = mgr.get_writer(spec["shuffle_id"], map_id)
        k = rng.integers(0, 1000, rows).astype(np.int64)
        w.write_partition(0, ColumnarBatch.from_pandas(
            pd.DataFrame({"k": k})))
        st = w.commit(1)
        outputs.append({"map_id": map_id,
                        "executor_id": st.executor_id,
                        "tcp_address": st.tcp_address,
                        "partition_sizes": st.partition_sizes})
    print("OUTPUTS:" + json.dumps(outputs), flush=True)
    while True:  # serve until killed
        time.sleep(0.2)
"""


def test_kill_server_process_mid_fetch_fetch_failed():
    """The serving executor PROCESS is killed while a transfer is in
    flight: the reader must drop partials, exhaust its bounded retries
    against the dead address, and surface FetchFailedError naming the
    peer — promptly, not after hanging (reference RapidsShuffleIterator
    error path on a lost UCX endpoint)."""
    import time as _time

    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.shuffle.client_server import FetchFailedError
    from spark_rapids_tpu.shuffle.manager import (
        MapOutputRegistry, MapStatus, TpuShuffleManager)

    shuffle_id = 4247
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", CHILD_SERVER],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=env, cwd=cwd)
    try:
        # map 0 is tiny (its batch completes fast -> we know the
        # stream is live); map 1 is ~200 throttled chunks (~2s), so
        # the kill below lands mid-transfer deterministically
        spec = {"shuffle_id": shuffle_id, "bounce": 4096,
                "map_rows": [64, 100_000]}
        proc.stdin.write((json.dumps(spec) + "\n").encode())
        proc.stdin.flush()
        line = b""
        deadline = _time.monotonic() + 180
        while not line.startswith(b"OUTPUTS:"):
            assert _time.monotonic() < deadline, "server never came up"
            line = proc.stdout.readline()
            assert line, proc.stderr.read().decode()[-2000:]
        outputs = json.loads(line.decode()[len("OUTPUTS:"):])

        conf = C.RapidsConf({
            "spark.rapids.shuffle.enabled": True,
            "spark.rapids.shuffle.fetch.maxRetries": 1,
            "spark.rapids.shuffle.fetch.backoff.baseMs": 1.0})
        with C.session(conf):
            mgr = TpuShuffleManager("executor-R")
            mgr.register_shuffle(shuffle_id)
            for o in outputs:
                MapOutputRegistry.register(shuffle_id, o["map_id"], MapStatus(
                    o["executor_id"], o["tcp_address"],
                    o["partition_sizes"]))
            t0 = _time.monotonic()
            got_rows = 0
            with pytest.raises(FetchFailedError) as ei:
                for b in mgr.get_reader(shuffle_id, 0, timeout=20.0):
                    got_rows += b.num_rows
                    if got_rows <= 64:  # first (tiny) batch landed
                        proc.kill()     # SIGKILL mid-stream of map 1
            elapsed = _time.monotonic() - t0
            assert elapsed < 30.0, f"FetchFailed took {elapsed:.1f}s"
            assert "tcp://" in str(ei.value)
            assert got_rows < 64 + 100_000, "full data despite kill?"
            mgr.unregister_shuffle(shuffle_id)
            mgr.close()
    finally:
        proc.kill()
        proc.wait(timeout=30)


def test_cross_process_dead_server_fetch_failed():
    """Fetching from a server that has gone away must surface the
    FetchFailed semantics (stage-retry signal), not hang (reference
    RapidsShuffleIterator error path)."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
    n_parts, shuffle_id = 2, 4246
    with C.session(C.RapidsConf({"spark.rapids.shuffle.enabled": True})):
        mgr = TpuShuffleManager("executor-A")
        mgr.register_shuffle(shuffle_id)
        outputs, _ = _write_maps(mgr, shuffle_id, n_parts, rng_seed=31)
        # kill the serving executor BEFORE the fetch
        mgr.close()
        proc = _spawn_reader({"shuffle_id": shuffle_id,
                              "num_partitions": n_parts,
                              "outputs": outputs,
                              "expect_fetch_failed": True,
                              "timeout": 6.0})
        out = proc.stdout.decode()
        assert proc.returncode == 0, \
            f"{out}\n{proc.stderr.decode()[-2000:]}"
        assert "FETCH_FAILED" in out, out
