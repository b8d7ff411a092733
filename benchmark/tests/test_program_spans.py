"""The readers of the program's spans: each on a hand-made list of spans
with a known answer, the cut to the window's jobs, what makes them
report nothing, and that only a traced run turns the tracer on."""

import os
import sys
import time

import pytest

from benchmark import harness
from benchmark.layer_metrics import JobSpan, Observations
from benchmark.tests import toy
from sparkdl_tpu.obs import trace

NEW_METRICS = ["image_decode_ms_per_image", "to_arrow_ms_per_image",
               "pack_in_ms_per_image", "pack_out_ms_per_image",
               "h2d_enqueue_ms_per_batch", "gather_host_ms_per_batch",
               "device_wait_share", "transform_self_share"]


@pytest.fixture(autouse=True)
def _tracer_off_and_environment_restored():
    saved = dict(os.environ)
    yield
    trace.configure(enabled=False)
    os.environ.clear()
    os.environ.update(saved)


@pytest.fixture()
def ps():
    from benchmark import program_spans

    program_spans.enable()
    trace.get_tracer().clear()
    return program_spans


def _span(name, start_s, dur_s, span_id, parent_id=None, thread="main",
          device_s=None, **attrs):
    d = {"name": name, "trace_id": "t1", "span_id": span_id,
         "parent_id": parent_id, "ts_us": start_s * 1e6,
         "dur_us": dur_s * 1e6, "thread": thread, "tid": 1, "status": "ok"}
    if device_s is not None:
        d["device_us"] = device_s * 1e6
    if attrs:
        d["attrs"] = attrs
    return d


def _job_spans(at, tag):
    """One job of 10 s from ``at``: 6 s of ``readImages`` over 8 files,
    4 s of ``transform`` in two dispatches."""
    s = lambda name, start, dur, sid, parent=None, **kw: _span(  # noqa: E731
        name, at + start, dur, f"{tag}{sid}", parent and f"{tag}{parent}",
        **kw)
    return [
        s("io.read_images", 0.0, 6.0, "r", files=8, rows=8),
        s("io.read", 0.1, 0.4, "r1", "r", files=8, bytes=800),
        s("io.decode", 0.5, 4.0, "r2", "r", rows=8, failed=0),
        s("io.to_arrow", 4.5, 0.8, "r3", "r", rows=8, bytes=4800),
        s("io.repartition", 5.3, 0.4, "r4", "r", rows=8, partitions=1),
        s("transform.run", 6.0, 4.0, "t", rows=8, valid_rows=8),
        s("transform.pack_in", 6.1, 0.4, "t1", "t", rows=4, valid=4),
        s("pipeline.run", 6.5, 3.0, "p", "t"),
        # two stage threads that overlap: 6.6-7.6 and 7.2-8.4 cover
        # 6.6-8.4, and the gathers 8.5-8.9 and 9.0-9.2
        s("pipeline.prepare", 6.6, 1.0, "p1", "p", thread="prepare"),
        s("transform.pack_in", 6.7, 0.8, "p2", "p1", thread="prepare",
          rows=4, valid=4),
        s("pipeline.dispatch", 7.2, 1.2, "p3", "p", thread="dispatch"),
        s("engine.dispatch", 7.2, 1.2, "p4", "p3", thread="dispatch"),
        s("engine.h2d", 7.3, 0.06, "p5", "p4", thread="dispatch",
          bytes=100),
        s("engine.h2d", 7.9, 0.02, "p6", "p4", thread="dispatch",
          bytes=100),
        s("pipeline.gather", 8.5, 0.4, "p7", "p", thread="gather",
          device_s=0.3, rows=4, bytes=64),
        s("pipeline.gather", 9.0, 0.2, "p8", "p", thread="gather",
          device_s=0.15, rows=4, bytes=64),
        s("transform.pack_out", 9.6, 0.3, "t2", "t", rows=8, values=64),
    ]


def _obs(jobs, window_s=20.0):
    return Observations(
        window_s=window_s,
        jobs=[JobSpan(a, b, 8, {"decode": 6.0, "transform": 4.0})
              for a, b in jobs],
        counters={}, config={}, peak={}, chips=1, trace=None)


#: by hand, over two such jobs (16 files, 4 dispatches, 20 s)
KNOWN = {
    "image_decode_ms_per_image": 1e3 * 8.0 / 16,
    "to_arrow_ms_per_image": 1e3 * (1.6 + 0.8) / 16,
    "pack_in_ms_per_image": 1e3 * 2.4 / 16,
    "pack_out_ms_per_image": 1e3 * 0.6 / 16,
    "h2d_enqueue_ms_per_batch": 1e3 * 0.16 / 4,
    "gather_host_ms_per_batch": 1e3 * 0.3 / 4,
    "device_wait_share": 100 * 0.9 / 20.0,
    # transform.run: 4.0 - (0.4 pack_in + 3.0 pipeline.run + 0.3 pack_out)
    # = 0.3; pipeline.run: 3.0 - (1.8 + 0.4 + 0.2) = 0.6
    "transform_self_share": 100 * (0.3 + 0.6) / 4.0,
}


def _play(ps, monkeypatch, spans):
    """The tracer's ring as the readers see it."""
    monkeypatch.setattr(trace.get_tracer(), "snapshot", lambda: list(spans))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_reader_on_spans_with_a_known_answer(ps, monkeypatch, metric):
    warm = _job_spans(85.0, "w")          # set-up's job, before the window
    spans = warm + _job_spans(100.0, "a") + _job_spans(110.0, "b")
    _play(ps, monkeypatch, spans)
    obs = _obs([(100.0, 110.0), (110.0, 120.0)])
    assert len(ps.in_window(obs)) == 2 * len(warm)
    assert harness.find_reader(metric).read(obs) == pytest.approx(
        KNOWN[metric], rel=1e-9)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_reader_reports_nothing_where_there_is_nothing_to_read(
        ps, monkeypatch, metric):
    read = harness.find_reader(metric).read
    obs = _obs([(100.0, 110.0)], window_s=10.0)
    spans = _job_spans(100.0, "a")
    _play(ps, monkeypatch, spans)
    assert read(obs) is not None
    assert read(_obs([])) is None                      # no job
    monkeypatch.setattr(trace.get_tracer(), "dropped", 1)
    assert read(obs) is None                           # the ring overflowed
    monkeypatch.setattr(trace.get_tracer(), "dropped", 0)
    assert read(obs) is not None
    # a cell without this layer (no files, the serial path), or the
    # parent's program, which has no such span
    _play(ps, monkeypatch, [s for s in spans if s["name"] in (
        "pipeline.run", "pipeline.prepare", "pipeline.dispatch",
        "engine.dispatch")])
    assert read(obs) is None
    trace.configure(enabled=False)
    assert read(obs) is None                           # the tracer is off


def test_a_ring_that_overflowed_is_refused(ps):
    tracer = trace.configure(enabled=True, capacity=4)
    for _ in range(6):
        tracer.span("io.decode", rows=1).finish()
    assert tracer.dropped == 2
    assert ps.in_window(_obs([(0.0, 1e12)])) is None
    # a program from before ``Tracer.dropped``: a full ring is refused
    del tracer.dropped
    assert ps.in_window(_obs([(0.0, 1e12)])) is None
    tracer.clear()
    tracer.span("io.decode", rows=1).finish()
    assert len(ps.in_window(_obs([(0.0, 1e12)]))) == 1


def test_self_time_is_less_the_union_of_descendants_on_any_thread(ps):
    spans = [
        _span("a.root", 0.0, 10.0, "r"),
        _span("a.child", 1.0, 4.0, "c1", "r", thread="one"),
        _span("a.child", 3.0, 4.0, "c2", "r", thread="two"),     # overlaps
        _span("a.leaf", 3.5, 5.5, "g", "c2", thread="two"),      # 3.5-9.0
        _span("a.late", 9.5, 2.0, "c3", "r", thread="two"),      # cut at 10
        _span("a.other", 0.0, 10.0, "x"),                        # no kin
    ]
    assert ps.self_s(spans, "a.root") == pytest.approx(10 - 8.0 - 0.5)
    assert ps.self_s(spans, "a.child") == pytest.approx(4.0 + 0.5)
    assert ps.self_s(spans, "a.other") == pytest.approx(10.0)
    assert ps.self_s(spans, "a.absent") == 0.0
    assert ps.total_s(spans, "a.child") == pytest.approx(8.0)
    assert ps.attr_sum(spans, "a.child", "rows") == 0


def test_enable_keeps_the_ring_it_has(ps):
    tracer = trace.get_tracer()
    assert tracer.enabled and tracer.capacity == ps.CAPACITY
    tracer.span("io.decode", rows=1).finish()
    ps.enable()
    assert trace.get_tracer() is tracer and len(tracer) == 1


def test_the_cell_lists_the_new_metrics_as_program_spans():
    cell = harness.load_cell(harness.ROOT, "inceptionv3.jpeg")
    mine = {m["name"]: m for m in cell.per_layer if m["name"] in NEW_METRICS}
    assert sorted(mine) == sorted(NEW_METRICS)
    assert {m["source"] for m in mine.values()} == {"program_span"}
    assert {m["moves"] for m in mine.values()} == {"images_per_s"}
    assert [m["name"] for m in cell.per_layer[-len(NEW_METRICS):]] \
        == NEW_METRICS


def test_an_untraced_run_leaves_the_tracer_off_and_a_traced_one_reads_it(
        tmp_path):
    """The toy cell's run is untraced: it imports no reader and no
    ``program_spans``, and the program's tracer stays off.  Importing
    the readers, as a traced run does before set-up, turns it on, and
    the same jobs then leave spans that every new reader reads."""
    gone = [m for m in sys.modules if m == "benchmark.program_spans"
            or m.rpartition(".")[2] in NEW_METRICS]
    for m in gone:
        del sys.modules[m]
    import benchmark

    vars(benchmark).pop("program_spans", None)   # or `from benchmark import` finds it
    trace.configure(enabled=False)
    root, peaks = toy.make_root(tmp_path)
    result = toy.run(root, peaks, "inceptionv3.jpeg")
    assert result["correct"] is True, result["checks"]
    assert "benchmark.program_spans" not in sys.modules
    assert not trace.get_tracer().enabled and len(trace.get_tracer()) == 0

    cell = harness.load_cell(root, "inceptionv3.jpeg")
    readers = {n: harness.find_reader(n) for n in NEW_METRICS}
    assert trace.get_tracer().enabled
    harness.install_weights(cell.config, 7)
    traffic = harness.find_generator(cell.traffic["generator"]).build(
        cell.traffic, cell.config, 7, str(tmp_path / "work"))
    traffic.run_job(traffic.warm_input)
    jobs = []
    t0 = time.perf_counter()
    for which in (0, 1):
        start = time.perf_counter()
        spans = traffic.run_job(traffic.inputs[which]).spans
        jobs.append(JobSpan(start, time.perf_counter(), traffic.job_images,
                            spans))
    obs = Observations(window_s=time.perf_counter() - t0, jobs=jobs,
                       counters={}, config=cell.config, peak={}, chips=1,
                       trace=None)
    harness.free_program_state()
    values = {n: r.read(obs) for n, r in readers.items()}
    assert all(v is not None and v >= 0 for v in values.values()), values
    # the spans agree with the benchmark's clock around the same call
    outside = harness.find_reader("decode_ms_per_image").read(obs)
    inside = (values["image_decode_ms_per_image"]
              + values["to_arrow_ms_per_image"])
    assert 0 < inside <= outside
    assert 0 < values["device_wait_share"] < 100
    assert 0 <= values["transform_self_share"] < 100
