"""The readers of set-up's spans (PR 39): over a ring filled by hand with
a known answer — the cut at the window's start, the parentless compile
left out, the self time of ``engine.build``, what makes them report
nothing — and over one toy run on the CPU, traced and untraced."""

import os

import pytest

from benchmark import harness
from benchmark.layer_metrics import JobSpan, Observations
from benchmark.tests import toy
from sparkdl_tpu.obs import trace

SETUP_METRICS = ["setup_trace_lower_s", "setup_compile_load_s",
                 "setup_engine_build_s"]
NEW_METRICS = SETUP_METRICS + ["compile_s_in_window"]
CELLS = ["inceptionv3.jpeg", "falcon_h1_34b.rows4k",
         "trinity_large_preview.rows16k", "sdar_30b_a3b_chat.gen256"]


@pytest.fixture(autouse=True)
def _tracer_off_and_environment_restored():
    saved = dict(os.environ)
    yield
    trace.configure(enabled=False)
    os.environ.clear()
    os.environ.update(saved)


@pytest.fixture()
def ring(monkeypatch):
    """The tracer as a traced run has it, its ring played from a list."""
    from benchmark import program_spans

    program_spans.enable()
    trace.get_tracer().clear()

    def play(spans):
        monkeypatch.setattr(trace.get_tracer(), "snapshot",
                            lambda: list(spans))

    return play


def _span(name, start_s, dur_s, span_id, parent_id=None, **attrs):
    return {"name": name, "trace_id": "t1", "span_id": span_id,
            "parent_id": parent_id, "ts_us": start_s * 1e6,
            "dur_us": dur_s * 1e6, "thread": "main", "tid": 1,
            "status": "ok", "attrs": attrs}


def _compile(at, tag, parent, program, trace_s, lower_s, backend_s, cache):
    """The three spans of one program's compile, back to back from
    ``at``."""
    return [
        _span("compile.trace", at, trace_s, tag + "t", parent,
              program=program),
        _span("compile.lower", at + trace_s, lower_s, tag + "l", parent,
              program=program),
        _span("compile.backend", at + trace_s + lower_s, backend_s,
              tag + "b", parent, program=program, cache=cache),
    ]


#: set-up from 50 s to the window's start at 100 s
SETUP = (
    # the benchmark's own draw of the weights: no parent, in no metric
    _compile(50.0, "d", None, "draw", 0.5, 0.25, 11.0, "hit")
    # an engine built in 3 s, of which a cast's compile covers 1 s
    + [_span("engine.build", 62.0, 3.0, "e", None, device_batch_size=8,
             param_bytes=24, jit_cached=False)]
    + _compile(62.5, "c", "e", "convert_element_type", 0.125, 0.125, 0.75,
               "hit")
    # the warm job: the cell's program under its first dispatch
    + [_span("transform.run", 70.0, 8.0, "w"),
       _span("engine.dispatch", 71.0, 6.0, "wd", "w", rows=8)]
    + _compile(71.0, "p", "wd", "apply", 2.0, 1.5, 2.25, "miss")
    # the clock's marker program, the benchmark's again
    + _compile(90.0, "m", None, "bench_clock_marker", 0.01, 0.01, 0.05,
               "hit")
)
#: by hand
KNOWN = {"setup_trace_lower_s": 0.125 + 0.125 + 2.0 + 1.5,
         "setup_compile_load_s": 0.75 + 2.25,
         "setup_engine_build_s": 3.0 - 1.0}


def _obs(jobs=((100.0, 110.0), (110.0, 120.0)), counters=None):
    return Observations(
        window_s=20.0,
        jobs=[JobSpan(a, b, 8, {"transform": b - a}) for a, b in jobs],
        counters=counters or {}, config={}, peak={}, chips=1, trace=None)


@pytest.mark.parametrize("metric", SETUP_METRICS)
def test_a_reader_on_a_ring_with_a_known_answer(ring, metric):
    # the window's own spans, a recompile among them, are not set-up's
    window = [_span("transform.run", 100.0, 10.0, "a"),
              _span("engine.dispatch", 101.0, 6.0, "ad", "a", rows=8)
              ] + _compile(101.0, "r", "ad", "apply", 1.0, 1.0, 3.0, "hit")
    ring(list(SETUP) + window)
    assert harness.find_reader(metric).read(_obs()) == pytest.approx(
        KNOWN[metric], rel=1e-9)


@pytest.mark.parametrize("metric", SETUP_METRICS)
def test_a_span_that_straddles_the_windows_start_is_not_counted(
        ring, metric):
    straddling = [
        _span("engine.dispatch", 98.0, 4.0, "sd", None, rows=8),
        _span("compile.trace", 99.0, 2.0, "st", "sd", program="apply"),
        _span("compile.backend", 99.5, 1.0, "sb", "sd", program="apply",
              cache="miss"),
        _span("engine.build", 99.0, 1.5, "se", None),
    ]
    ring(list(SETUP) + straddling)
    assert harness.find_reader(metric).read(_obs()) == pytest.approx(
        KNOWN[metric], rel=1e-9)


@pytest.mark.parametrize("metric", SETUP_METRICS)
def test_a_parentless_compile_is_left_out_and_none_reads_zero(ring, metric):
    read = harness.find_reader(metric).read
    ring(_compile(50.0, "d", None, "draw", 0.5, 0.25, 11.0, "miss"))
    # the tracer was on and set-up left the program's spans nothing: a
    # number, since a cell listed for a metric must read one
    assert read(_obs()) == 0.0
    ring([])
    assert read(_obs()) == 0.0


@pytest.mark.parametrize("metric", SETUP_METRICS)
def test_a_reader_reports_nothing_where_there_is_nothing_to_read(
        ring, monkeypatch, metric):
    read = harness.find_reader(metric).read
    ring(SETUP)
    assert read(_obs()) is not None
    assert read(_obs(jobs=())) is None                 # no job
    monkeypatch.setattr(trace.get_tracer(), "dropped", 1)
    assert read(_obs()) is None                        # the ring overflowed
    monkeypatch.setattr(trace.get_tracer(), "dropped", 0)
    assert read(_obs()) is not None
    # the parent's program: its tracer has no ``record``, so no compile
    # ever left a span and a 0.0 would say nothing
    monkeypatch.delattr(trace.Tracer, "record")
    assert read(_obs()) is None
    monkeypatch.undo()
    trace.configure(enabled=False)
    assert read(_obs()) is None                        # the tracer is off


def test_compile_s_in_window_adds_the_three_counters():
    read = harness.find_reader("compile_s_in_window").read
    counters = {"compile_cache.hits": 1.0, "compile_cache.misses": 0.0,
                "compile_cache.trace_s": 0.5, "compile_cache.lower_s": 0.25,
                "compile_cache.backend_s": 0.125,
                "compile_cache.load_s": 0.1, "compile_cache.saved_s": 9.0}
    assert read(_obs(counters=counters)) == 0.875
    assert read(_obs(counters={k: 0.0 for k in counters})) == 0.0
    # the parent's program counts hits and misses alone
    assert read(_obs(counters={"compile_cache.hits": 0.0,
                               "compile_cache.misses": 0.0})) is None


def test_every_cell_lists_the_new_metrics_last():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as fh:
        import json

        bench = json.load(fh)
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW_METRICS
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == CELLS
        assert m["moves"] == ("images_per_s"
                              if m["name"] == "compile_s_in_window"
                              else "setup_s")
    # the first per-layer metrics under ``setup_s``
    assert {m["name"] for m in bench["per_layer"]
            if m["moves"] == "setup_s"} == set(SETUP_METRICS)


def test_a_traced_toy_run_reads_all_four_and_an_untraced_one_none(
        tmp_path, monkeypatch):
    """One toy run on the CPU: the program's compiles at set-up reach the
    readers through the tracer ``run_cell`` turned on, the window
    compiles nothing, and an untraced line carries none of the four."""
    from sparkdl_tpu.parallel import compile_cache

    trace.configure(enabled=False)
    root, peaks = toy.make_root(tmp_path)
    plain = toy.run(root, peaks, "inceptionv3.jpeg")
    assert plain["correct"] is True, plain["checks"]
    assert not set(NEW_METRICS) & set(plain["metrics"])
    assert not trace.get_tracer().enabled
    # the seconds moved all the same (the tracer was off)
    assert compile_cache.stats()["backend_s"] > 0.0

    monkeypatch.setattr(harness, "DeviceTrace", toy.MadeUpDeviceTrace)
    # a cell whose program this process has not met: its warm job compiles
    traced = toy.run(root, peaks, "inceptionv3.f32.structs", trace=True)
    assert traced["correct"] is True, traced["checks"]
    values = {n: traced["metrics"][n]["value"] for n in NEW_METRICS}
    assert all(isinstance(v, float) for v in values.values()), values
    assert values["setup_trace_lower_s"] > 0.0
    assert values["setup_compile_load_s"] > 0.0
    assert values["setup_engine_build_s"] > 0.0
    assert values["compile_s_in_window"] == 0.0
    assert traced["compiles_in_window"] == 0
    assert all(traced["metrics"][n]["unit"] == "s" for n in NEW_METRICS)
    # together they are part of set-up, not more than the warm job's mark
    # and the weights' (an engine may be built with the weights)
    marks = traced["setup_seconds"]
    assert sum(values[n] for n in SETUP_METRICS) <= (
        marks["warm_job"] + marks["weights"] + marks["traffic"])
    # the window's spans are as they were: no compile.* names a gap
    assert not [name for name, _ in traced["breakdown"]["idle_gaps"]
                if name.startswith("compile.")]
