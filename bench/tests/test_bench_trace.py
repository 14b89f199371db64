"""The trace reducers on a small trace recorded on a v5e (two PageRank
supersteps at Graph500 scale 22, cut to nine instructions), and the
roofline byte counts from shapes."""
import json
import re
from pathlib import Path

import pytest

from bench import harness, roofline, tracedata

FIXTURE = json.loads((Path(__file__).parent / "fixtures" /
                      "trace_pagerank_v5e.json").read_text())
WINDOW_NS = 33607122639.0
SLOTS, VERTICES = 134217728, 4194304
RECEIVERS = 2500000
FOLD_NS = 5167820 + 5168120


def _record(trace=FIXTURE, **kw):
    fields = dict(cell="g500-s22.pagerank", setup_s=1.0, window_s=1.0,
                  window_wall=(0.0, 1.0), work=0, steps=2,
                  edge_slots=SLOTS, vertices=VERTICES, peak_bytes=None,
                  peaks={"hbm_bytes_per_s": 819e9}, value_channels=2,
                  receivers=RECEIVERS, compile_spans=[], trace=trace)
    fields.update(kw)
    return harness.RunRecord(**fields)


def _read(name, record):
    return harness.metric_reader(name)(record)


def test_trace_parts():
    assert [p["name"] for p in tracedata.device_planes(FIXTURE)] == \
        ["/device:TPU:0"]
    lo, hi = tracedata.window_ns(FIXTURE)
    assert hi - lo == WINDOW_NS
    assert tracedata.programs_in_window(FIXTURE, "jit_superstep") == 2
    sorts = tracedata.ops_in_window(FIXTURE, re.compile(r"sort(\.\d+)?$"))
    assert len(sorts) == 4


def test_program_ops_split_the_window_by_superstep():
    runs = tracedata.program_ops(FIXTURE, "jit_superstep")
    assert [d for d, _ in runs] == [16786226434.0, 16812643542.0]
    assert [ops["sort.10"] for _, ops in runs] == [3044446529.0,
                                                   3046477366.0]
    assert all(len(ops) == 9 for _, ops in runs)


def test_idle_share_and_device_time():
    busy_ns = 32116090770.0   # the 18 operations, none overlapping
    assert _read("idle_share", _record()) == pytest.approx(
        100 * (1 - busy_ns / WINDOW_NS), rel=1e-12)
    busy, window = tracedata.device_time(FIXTURE)
    assert busy == pytest.approx(busy_ns / 1e9)
    assert window == pytest.approx(WINDOW_NS / 1e9)


def test_sort_ms_per_step():
    sort_ns = 3044446529 + 3046477366 + 377381351 + 377354166
    assert _read("sort_ms_per_step", _record()) == pytest.approx(
        sort_ns / 1e6 / 2)


def test_kernel_rooflines():
    gather_ns = 104867862 + 104874967
    gather_bytes = 4 * (SLOTS * 3 + VERTICES * 2)
    assert _read("edge_gather.roofline", _record()) == pytest.approx(
        100 * 2 * gather_bytes / 819e9 / (gather_ns / 1e9))
    # keys and payload read per slot, keys and folded payload written per
    # receiving vertex
    fold_bytes = 4 * (SLOTS * 2 + RECEIVERS * 2)
    assert _read("sender_fold.roofline", _record()) == pytest.approx(
        100 * 2 * fold_bytes / 819e9 / (FOLD_NS / 1e9))


@pytest.mark.parametrize("slots,receivers,rows,segments", [
    (SLOTS, RECEIVERS, SLOTS, RECEIVERS),
    # a stream whose capacity exceeds the live messages counts the live
    (1000, 10, 1000, 10),
    # never more segments than rows
    (1000, 5000, 1000, 1000),
])
def test_sender_fold_counts_live_rows_not_capacity(slots, receivers, rows,
                                                    segments):
    got = _read("sender_fold.roofline",
                _record(edge_slots=slots, receivers=receivers))
    need = 2 * roofline.sender_fold_bytes(rows, segments, 1)
    assert got == pytest.approx(100 * need / 819e9 / (FOLD_NS / 1e9))


def test_readers_find_nothing_without_a_device_trace():
    for name in ("idle_share", "sort_ms_per_step", "edge_gather.roofline",
                 "sender_fold.roofline"):
        assert _read(name, _record(trace=None)) is None
        assert _read(name, _record(trace={"planes": []})) is None


def test_breakdown_names_ops_and_idle_gaps():
    b = tracedata.breakdown(FIXTURE)
    names = [n for n, _ in b["device_ops"]]
    assert len(names) <= 10 and names[0].startswith("sort.10 = ")
    assert b["device_ops"][0][1] == pytest.approx(6.090923895)
    assert len(b["idle_gaps"]) == 10
    assert all(s > 0 for _, s in b["idle_gaps"])
    assert b["idle_gaps"] == sorted(b["idle_gaps"], key=lambda g: -g[1])


def test_operand_shapes_from_hlo_text():
    fold = next(e[0] for p in tracedata.device_planes(FIXTURE)
                for e in tracedata.line_events(p, tracedata.OPS_LINE)
                if tracedata.op_name(e[0]) == "segment_combine.1")
    assert tracedata.operand_shapes(fold) == [
        ("s32", (1, 1048576, 128)), ("f32", (1, 1, 1048576, 128))]


def test_compile_seconds_merge_nested_spans_inside_the_window():
    spans = [(9.0, 10.5), (10.0, 10.2), (10.1, 10.4), (12.0, 13.0),
             (20.0, 21.0)]
    rec = _record(compile_spans=spans, window_wall=(10.0, 12.5))
    assert _read("compile_s.window", rec) == pytest.approx(0.5 + 0.5)
    assert _read("compile_s.window", _record()) == 0.0


def test_roofline_bytes_from_shapes():
    assert roofline.edge_gather_bytes(SLOTS, VERTICES, 2) == 1644167168
    assert roofline.sender_fold_bytes(SLOTS, 0, 1) == 1073741824
    # two channels, 1000 rows into 10 segments: key and payload each way
    assert roofline.sender_fold_bytes(1000, 10, 2) == 4 * 1010 * 3
    assert roofline.roofline_pct(0, 0.0, 1e9) is None
    assert roofline.roofline_pct(2 * 819e9, 4.0, 819e9) == pytest.approx(50)
