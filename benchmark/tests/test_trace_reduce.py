"""The reduction from a trace to numbers, on a synthetic trace whose
answer is known by hand (times in nanoseconds)."""

import pytest

from benchmark import trace_reduce as tr

MS = 1e6

# one device: fusion.1 runs 10..30, a nested child 12..18 inside it,
# fusion.2 overlaps its tail 25..40, then a lone copy 70..80, and one op
# 95..120 that runs out of the window (window = 0..100)
OPS = [("fusion.1", 10 * MS, 20 * MS), ("child", 12 * MS, 6 * MS),
       ("fusion.2", 25 * MS, 15 * MS), ("copy", 70 * MS, 10 * MS),
       ("fusion.1", 95 * MS, 25 * MS)]
MODULES = [("jit_bench_clock_marker(7)", -6 * MS, 2 * MS),
           ("jit_fn(1)", 10 * MS, 30 * MS), ("jit_fn(1)", 70 * MS, 10 * MS),
           ("jit_fn(1)", 95 * MS, 25 * MS)]
ANNOTATIONS = [("bench.decode", 0.0, 9 * MS),
               ("bench.transform", 9 * MS, 85 * MS),
               ("bench.inner", 41 * MS, 20 * MS)]
WINDOW = (0.0, 100 * MS)


def test_busy_union_counts_nested_and_overlapping_once():
    assert tr.busy_union(OPS[:3]) == 30 * MS          # 10..40
    assert tr.busy_union(tr.clip(OPS, WINDOW)) == 45 * MS   # + 70..80, 95..100
    assert tr.busy_union([]) == 0.0


def test_merge_gives_disjoint_sorted_intervals():
    assert tr.merge(tr.clip(OPS, WINDOW)) == [
        (10 * MS, 40 * MS), (70 * MS, 80 * MS), (95 * MS, 100 * MS)]


def test_totals_by_name_and_top():
    totals = tr.totals_by_name(tr.clip(OPS, WINDOW))
    assert totals == {"fusion.1": 25 * MS, "child": 6 * MS,
                      "fusion.2": 15 * MS, "copy": 10 * MS}
    assert tr.top(totals, 2) == [("fusion.1", 25 * MS), ("fusion.2", 15 * MS)]


def test_idle_gaps_are_labelled_by_the_covering_annotation():
    gaps = tr.idle_gaps(tr.merge(tr.clip(OPS, WINDOW)), WINDOW, ANNOTATIONS)
    # 0..10: decode covers 9 of it, transform 1; 40..70: transform covers
    # all 30 and the nested bench.inner only 20; 80..95: transform 14
    assert gaps == [("bench.decode", 0.0, 10 * MS),
                    ("bench.transform", 40 * MS, 30 * MS),
                    ("bench.transform", 80 * MS, 15 * MS)]


def test_a_gap_inside_nested_annotations_goes_to_the_innermost():
    busy = [(0.0, 45 * MS), (55 * MS, 100 * MS)]
    assert tr.idle_gaps(busy, WINDOW, ANNOTATIONS) == [
        ("bench.inner", 45 * MS, 10 * MS)]


def test_a_gap_under_no_annotation_is_named_so():
    assert tr.idle_gaps([], (200 * MS, 210 * MS), ANNOTATIONS) == [
        ("bench.unannotated", 200 * MS, 10 * MS)]


def test_reduce_trace_window_busy_modules_and_breakdown():
    trace = tr.Trace({"/device:TPU:0": OPS}, {"/device:TPU:0": MODULES})
    r = tr.reduce_trace(trace, 1, WINDOW, ANNOTATIONS)
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.045)
    assert r.module_executions == 3
    assert r.module_s == pytest.approx(0.030 + 0.010 + 0.005)
    assert r.device_ops[0] == ("fusion.1", pytest.approx(0.025))
    assert dict(r.idle_gaps) == {"bench.transform": pytest.approx(0.045),
                                 "bench.decode": pytest.approx(0.010)}


def test_reduce_trace_averages_over_the_chips_used():
    trace = tr.Trace(
        {"/device:TPU:0": OPS, "/device:TPU:1": [("copy", 0.0, 10 * MS)],
         "/device:TPU:2": []}, {})
    assert tr.reduce_trace(trace, 2, WINDOW, ANNOTATIONS).busy_s == \
        pytest.approx((0.045 + 0.010) / 2)


def test_the_clock_marker_ties_the_hosts_clock_to_the_traces():
    trace = tr.Trace({}, {"/device:TPU:0": MODULES})
    # the host ran the marker at 1000 ms on its clock, the device at
    # -5 ms (its middle) on the trace's
    assert tr.clock_offset_ns(trace, 1000 * MS) == -1005 * MS
    with pytest.raises(ValueError, match="bench_clock_marker"):
        tr.clock_offset_ns(tr.Trace({}, {"/device:TPU:0": MODULES[1:]}), 0.0)


def test_reduce_trace_refuses_a_trace_without_a_device():
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce_trace(tr.Trace({}, {}), 1, WINDOW, ANNOTATIONS)


@pytest.mark.parametrize("hlo,short", [
    ("%fusion.685 = f32[512,147,147,64]{0,3,2,1:T(8,128)} fusion(bf16[512,"
     "147,147,32]{0,3,2,1:T(8,128)(2,1)} %fusion.24), kind=kOutput",
     "%fusion.685 f32[512,147,147,64] fusion"),
    ("%copy-start = (f32[3,3,512,512]{3,2,1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
     "copy-start(f32[3,3,512,512]{3,2,1,0:T(8,128)} %p)",
     "%copy-start f32[3,3,512,512] copy-start"),
    ("not an HLO line", "not an HLO line"),
])
def test_an_operation_is_named_by_name_shape_and_kind(hlo, short):
    assert tr.short_op_name(hlo) == short
