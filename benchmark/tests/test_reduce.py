"""The reduction from a trace to numbers, on planes written out by hand
and on a small trace recorded on the chip."""
import os

import pytest

from benchmark.reduce import least_bytes as LB
from benchmark.reduce import peaks as PK
from benchmark.reduce import trace as TR

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "q6_v5e_small.xplane.pb.gz")


def test_union_clip_gaps():
    assert TR.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert TR.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]
    assert TR.gaps([[2, 3], [5, 6]], 0, 10) == [[0, 2], [3, 5], [6, 10]]


def test_reduce_planes_by_hand():
    s = 1e9
    planes = {
        "devices": {
            "/device:TPU:0": [("a", 1 * s, 2 * s), ("b", 1.5 * s, 3 * s),
                              ("a", 6 * s, 7 * s)],
            "/device:TPU:1": [("c", 2 * s, 2.5 * s)]},
        "spans": [("bench:accelerate", 0, 1 * s),
                  ("bench:collect", 1 * s, 10 * s),
                  ("exec:HashJoin", 3 * s, 6.2 * s)]}
    r = TR.reduce_planes(planes)
    assert r["window_s"] == 10.0 and r["chips"] == 2
    assert r["busy_s_busiest"] == 3.0            # [1,3) and [6,7)
    assert r["busy_s_mean"] == (3.0 + 0.5) / 2
    assert r["device_ops"][:2] == [["a", 2.0], ["b", 1.5]]
    gaps = dict(map(tuple, r["idle_gaps"]))
    # [0,1) under accelerate, [3,6) under the join, [7,10) under collect
    assert gaps == {"bench:accelerate": 1.0, "exec:HashJoin": 3.0,
                    "bench:collect": 3.0}
    assert TR.reduce_planes({"devices": {}, "spans": []}) == {}
    # a share of a roofline is never made up: no busy time, nothing read
    assert TR.reduce_planes({"devices": {"/device:TPU:0": []},
                             "spans": planes["spans"]}) == {}


def test_short_op_names():
    long = ("%fusion.2 = s32[65536]{0:T(1024)S(1)} fusion(s32[65536]{0:T("
            "1024)S(1)} %reshape.283), kind=kCustom, calls=%fused.2")
    assert TR.short_op(long, "jit_kernel") == \
        "jit_kernel/%fusion.2 s32[65536] fusion kCustom"
    assert len(TR.short_op("x" * 500)) <= 96


def test_peaks_are_a_table_with_no_default():
    assert PK.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        PK.peaks_of("cpu")


def test_least_bytes_of_q6():
    from benchmark.gen import tpch
    from benchmark import manifest as MF
    ref = MF.module_at("reference", "tpch_q6.py")
    t = tpch.generate(1, 1000, ["lineitem"])
    # shipdate int32 + three float64 columns, once, plus one float64
    assert LB.query_least_bytes(t, ref.READS, ref.answer(t)) == \
        1000 * (4 + 8 + 8 + 8) + 8
    assert LB.rows_read(t, ref.READS) == 1000


def test_recorded_trace_reduces_to_known_numbers():
    planes = TR.read_planes(RECORDED)
    assert list(planes["devices"]) == ["/device:TPU:0"]
    r = TR.reduce_planes(planes)
    expected = EXPECTED
    for key in ("window_s", "busy_s_busiest", "busy_s_mean"):
        assert r[key] == pytest.approx(expected[key], rel=1e-9), key
    assert r["device_ops"][0][0] == expected["top_op"]
    assert r["idle_gaps"][0][0] == expected["top_gap"]


#: what `reduce_planes` read from the recorded trace when it was checked
#: in: TPC-H q6 at 20,000 rows, one query, one v5e chip (my chip run,
#: PR 26: what `benchmark/tests/record_trace.py --workload sf025-q6-scan
#: --scale 20000 --seconds 0.1` does)
EXPECTED = {"window_s": 0.03103177, "busy_s_busiest": 0.002290237,
            "busy_s_mean": 0.002290237,
            "top_op": "jit_kernel/%fusion f32[16384] fusion kCustom",
            "top_gap": "bench:accelerate"}


def test_window_shape_names_the_slow_queries_and_the_collector():
    import gc

    from benchmark import manifest as MF
    from benchmark.reduce import window as WIN
    clock = WIN.GcClock()
    gc.callbacks.append(clock)
    gc.collect()
    gc.callbacks.remove(clock)
    assert clock.runs == [0, 0, 1] and clock.longest > 0
    rec, t = [], 100.0
    for i in range(40):
        took = 0.5 if i == 7 else 0.1
        rec.append({"query": 6, "asked": t, "start": t,
                    "planned": t + 0.06, "end": t + took,
                    "cpu": 0.2 * (i + 1)})
        t += took
    got = WIN.shape({"records": rec, "opened": 100.0}, 4.0, clock)
    assert got["queries"] == 40 and got["slow_queries"] == 1
    assert got["slow"][0]["at_s"] == 0.7 and got["slow"][0]["ms"] == 500.0
    assert got["slow"][0]["cpu_s"] == 0.2
    assert sum(n[0] for n in got["slices_n_p50_max"]) == 40
    reader = MF.module_at("layer_metrics", "gc_ms_per_query.py")
    assert reader.read({"records": rec, "gc": {"seconds": [0.01, 0.01,
                                                           0.02]}}) == 1.0
    assert reader.read({"records": [], "gc": clock.read()}) is None
