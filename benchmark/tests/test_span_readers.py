"""The five readers of the program's upload, plan and readback spans, on
spans written out by hand and on a small trace recorded on the chip."""
import os
import statistics

import pytest

from benchmark import manifest as MF
from benchmark.reduce import spans as SP
from benchmark.reduce import trace as TR

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "q6_v5e_spans.xplane.pb.gz")
READERS = ("upload_ms", "upload_put_ms", "upload_gb_per_s", "plan_self_ms",
           "readback_ms")
MS = 1_000_000


def reader(name):
    return MF.module_at("layer_metrics", name + ".py").read


def ctx_of(spans, traced=(6, 6), least=28_000_000):
    return {"planes": {"devices": {}, "spans": spans},
            "trace": {"traced": list(traced)},
            "queries": {6: {"least_bytes": least}}}


def one_query(t0, convert_ms, put_ms, plan_ms, readback_ms, chunks=2):
    """The spans of one query from `t0`: the planner's time split before
    and after one source's upload, whose partitions (`chunks` of them)
    run back to back, each a convert and a put; then collect with its
    two readback spans nested in it.  Times in ms; returns (spans,
    end)."""
    at = t0 + plan_ms * MS // 2
    up0, spans = at, []
    for _ in range(chunks):
        spans.append(("exec:upload-convert", at, at + convert_ms * MS))
        at += convert_ms * MS
        spans.append(("exec:upload-put", at, at + put_ms * MS))
        at += put_ms * MS
    spans.append(("exec:SourceUpload[s0]", up0, at))
    at += plan_ms * MS - plan_ms * MS // 2
    spans.append(("bench:accelerate", t0, at))
    c0 = at
    at += 5 * MS                                  # dispatch
    spans.append(("exec:HashAggregateExec[p0]", c0, at))
    spans.append(("exec:Readback", at, at + readback_ms * MS))
    at += readback_ms * MS
    spans.append(("exec:Readback", at, at + 1 * MS))
    at += 1 * MS
    spans.append(("bench:collect", c0, at))
    return spans, at


def test_readers_on_two_queries_back_to_back():
    a, end = one_query(1_000 * MS, convert_ms=3, put_ms=1, plan_ms=4,
                       readback_ms=7)
    b, _ = one_query(end, convert_ms=5, put_ms=2, plan_ms=6, readback_ms=9)
    ctx = ctx_of(a + b)
    # uploads 2 x (3+1) = 8 and 2 x (5+2) = 14: the median of two
    assert reader("upload_ms")(ctx) == 11.0
    assert reader("upload_put_ms")(ctx) == 3.0          # 2 and 4
    assert reader("plan_self_ms")(ctx) == 5.0           # 4 and 6
    assert reader("readback_ms")(ctx) == 9.0            # 7+1 and 9+1
    # 28 MB in 8 ms and in 14 ms
    assert reader("upload_gb_per_s")(ctx) == pytest.approx(
        statistics.median([28e6 / 8e6, 28e6 / 14e6]))


def test_a_span_outside_every_query_is_no_query_s():
    a, end = one_query(0, 3, 1, 4, 7)
    stray = [("exec:SourceUpload[s0]", end + MS, end + 50 * MS),
             ("exec:Readback", end + MS, end + 50 * MS)]
    ctx = ctx_of(a + stray, traced=(6,))
    assert reader("upload_ms")(ctx) == 8.0
    assert reader("readback_ms")(ctx) == 8.0


def test_two_sources_of_one_query_add_up_and_plan_takes_their_union():
    spans = [("bench:accelerate", 0, 20 * MS),
             ("exec:SourceUpload[s0]", 2 * MS, 8 * MS),
             ("exec:SourceUpload[s1]", 10 * MS, 15 * MS),
             ("bench:collect", 20 * MS, 30 * MS),
             ("exec:Readback", 28 * MS, 30 * MS)]
    ctx = ctx_of(spans, traced=(6,))
    assert reader("upload_ms")(ctx) == 11.0
    assert reader("plan_self_ms")(ctx) == 9.0
    assert reader("upload_put_ms")(ctx) is None         # no such span


@pytest.mark.parametrize("name", READERS)
def test_a_query_with_no_span_of_the_program_reads_nothing(name):
    """The parent of the PR that brought the spans: only `bench:`."""
    spans = [("bench:accelerate", 0, 100 * MS),
             ("bench:collect", 100 * MS, 150 * MS),
             ("exec:HashAggregateExec[p0]", 100 * MS, 140 * MS)]
    assert reader(name)(ctx_of(spans, traced=(6,))) is None
    assert reader(name)({"planes": {}, "trace": {}, "queries": {}}) is None


def test_a_query_without_an_upload_is_left_out_of_the_median():
    a, end = one_query(0, 3, 1, 4, 7)
    bare = [("bench:accelerate", end, end + 2 * MS),
            ("bench:collect", end + 2 * MS, end + 4 * MS)]
    ctx = ctx_of(a + bare)
    assert reader("upload_ms")(ctx) == 8.0
    assert reader("plan_self_ms")(ctx) == 4.0
    assert reader("upload_gb_per_s")(ctx) == pytest.approx(28e6 / 8e6)


def test_rate_is_not_read_when_queries_and_spans_do_not_pair():
    a, _ = one_query(0, 3, 1, 4, 7)
    assert reader("upload_gb_per_s")(ctx_of(a, traced=(6, 6))) is None
    assert reader("upload_gb_per_s")(ctx_of(a, traced=(6,),
                                            least=None)) is None


def test_every_reader_is_a_per_layer_entry_of_both_cells():
    entries = {m["name"]: m for m in MF.load()["per_layer"]}
    for name in READERS:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["workloads"] == ["sf025-q6-scan",
                                              "sf1-q6-scan"]
    assert SP.ACCELERATE.startswith(TR.SPAN_PREFIXES[1])


def test_recorded_trace_reads_all_five_and_they_add_up():
    """TPC-H q6 at 200,000 rows, one v5e chip, the PR that brought the
    spans (`record_trace.py --workload sf025-q6-scan --scale 200000
    --seconds 0.1`)."""
    planes = TR.read_planes(RECORDED)
    reduced = TR.reduce_planes(planes)
    n = sum(1 for name, _, _ in planes["spans"] if name == SP.ACCELERATE)
    assert n >= 1
    ctx = ctx_of(planes["spans"], traced=(6,) * n, least=200_000 * 28 + 8)
    got = {name: reader(name)(ctx) for name in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["upload_put_ms"] < got["upload_ms"]
    accel = statistics.median(
        (e - s) / 1e6 for name, s, e in planes["spans"]
        if name == SP.ACCELERATE)
    assert got["upload_ms"] + got["plan_self_ms"] == pytest.approx(
        accel, rel=0.03)
    assert not any(op.startswith("jit_kernel/")
                   for op, _ in reduced["device_ops"])
    assert reduced["idle_gaps"][0][0].startswith("exec:")
