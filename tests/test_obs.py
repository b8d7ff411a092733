"""Observability tests (sparkdl_tpu.obs — ISSUE 3).

Contracts pinned here:
  * the ``SPARKDL_TRACE`` gate and the near-zero DISABLED path (shared
    null-span singleton, empty ring, ``block_until_ready`` pass-through
    that never blocks);
  * END-TO-END NESTING (the acceptance criterion): a CPU-backend run —
    one serving request wave and one ``map_batches`` call — produces a
    valid Chrome-trace JSON whose spans nest serving → batcher →
    engine → pipeline-stage with the child-window-within-parent-window
    invariant;
  * the >= 1.5x overlap contract still holds WITH tracing ON;
  * exporters: Chrome JSON round-trip, span JSONL + ``load_spans`` on
    both artifact forms, Prometheus text exposition, metrics snapshot
    stable schema;
  * ``Metrics``: deterministic timing-vs-histogram percentile lookup
    (the name-collision satellite) and no lost counts / bounded series
    under concurrent writers (admission + dispatch + stage threads);
  * slow-request exemplars (top-K span trees) and ``Server.varz``;
  * ``tools/trace_summary.py`` folds both artifact forms;
  * ``bench.py`` per-config lines carry a FRESH metrics snapshot and a
    trace artifact path.
"""

import json
import logging
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from sparkdl_tpu import obs
from sparkdl_tpu.obs.trace import NULL_SPAN
from sparkdl_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _restore_tracer():
    """Every test leaves the process tracer the way the environment
    configures it (disabled in the test env)."""
    yield
    obs.configure_from_env()


def _fn(variables, x):
    import jax.numpy as jnp

    return jnp.tanh(x @ variables["w"])


@pytest.fixture(scope="module")
def model():
    rng = np.random.default_rng(7)
    return {"w": rng.normal(size=(12, 5)).astype(np.float32)}, \
        rng.normal(size=(50, 12)).astype(np.float32)


def _assert_child_within_parent(spans):
    """THE nesting invariant: every recorded child's [start, end] window
    sits inside its parent's (1 us epsilon for rounding)."""
    by_id = {s["span_id"]: s for s in spans}
    checked = 0
    for s in spans:
        p = by_id.get(s["parent_id"])
        if p is None:
            continue
        assert p["ts_us"] - 1 <= s["ts_us"], (s, p)
        assert (s["ts_us"] + s["dur_us"]
                <= p["ts_us"] + p["dur_us"] + 1), (s, p)
        checked += 1
    return checked


def _chains(spans, leaf_name):
    """Name paths leaf -> root for every span named ``leaf_name``."""
    by_id = {s["span_id"]: s for s in spans}
    out = []
    for s in spans:
        if s["name"] != leaf_name:
            continue
        path, cur = [], s
        while cur is not None:
            path.append(cur["name"])
            cur = by_id.get(cur["parent_id"])
        out.append(tuple(path))
    return out


# -- gate + disabled path --------------------------------------------------

def test_trace_env_gate(monkeypatch):
    from sparkdl_tpu.obs.trace import tracing_from_env

    for off in ("", "0", "false", "OFF", "no"):
        monkeypatch.setenv("SPARKDL_TRACE", off)
        assert tracing_from_env() == (False, None)
    for on in ("1", "true", "ON", "yes"):
        monkeypatch.setenv("SPARKDL_TRACE", on)
        assert tracing_from_env() == (True, None)
    monkeypatch.setenv("SPARKDL_TRACE", "/tmp/some/dir")
    assert tracing_from_env() == (True, "/tmp/some/dir")
    monkeypatch.delenv("SPARKDL_TRACE", raising=False)
    assert tracing_from_env() == (False, None)


def test_disabled_path_is_null_and_recordless():
    tracer = obs.configure(enabled=False)
    sp = tracer.span("anything", rows=3)
    assert sp is NULL_SPAN                      # one shared no-op object
    assert tracer.start_span("x") is NULL_SPAN
    with sp as inner:
        assert inner is NULL_SPAN
        inner.annotate(k=1)
        marker = object()
        # never blocks, never touches jax — returns the value untouched
        assert inner.block_until_ready(marker) is marker
    sp.finish()
    assert len(tracer) == 0 and tracer.snapshot() == []
    assert obs.current_trace_id() is None


def test_disabled_span_calls_are_cheap():
    """~50k disabled instrumentation hits in well under a second — an
    ultra-generous 20 us/call budget that still catches accidental
    O(ring) or locking work sneaking onto the disabled path."""
    import time

    tracer = obs.configure(enabled=False)
    t0 = time.perf_counter()
    for _ in range(50_000):
        with tracer.span("hot"):
            pass
    assert time.perf_counter() - t0 < 1.0


# -- span mechanics --------------------------------------------------------

def test_span_nesting_ids_ring_and_clear():
    tracer = obs.configure(enabled=True)
    with tracer.span("outer", a=1) as outer:
        assert tracer.current() is outer
        assert obs.current_trace_id() == outer.trace_id
        with tracer.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    assert tracer.current() is None
    spans = tracer.snapshot()
    assert [s["name"] for s in spans] == ["inner", "outer"]  # finish order
    assert spans[1]["attrs"] == {"a": 1}
    assert spans[0]["parent_id"] == spans[1]["span_id"]
    assert _assert_child_within_parent(spans) == 1
    tracer.clear()
    assert tracer.snapshot() == []


def test_ring_is_bounded():
    tracer = obs.configure(enabled=True, capacity=8)
    for i in range(30):
        with tracer.span("s", i=i):
            pass
    spans = tracer.snapshot()
    assert len(spans) == 8
    assert [s["attrs"]["i"] for s in spans] == list(range(22, 30))


def test_cross_thread_start_span_and_use():
    """start_span + use: the cross-thread continuation pattern serving
    uses (request opened on the caller thread, children created on a
    worker)."""
    tracer = obs.configure(enabled=True)
    root = tracer.start_span("root")
    seen = {}

    def worker():
        with tracer.use(root):
            with tracer.span("child") as c:
                seen["trace"] = c.trace_id
                seen["parent"] = c.parent_id

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    root.finish()
    root.finish("error")  # idempotent: second finish is a no-op
    assert seen["trace"] == root.trace_id
    assert seen["parent"] == root.span_id
    spans = tracer.snapshot()
    assert [s["name"] for s in spans] == ["child", "root"]
    assert spans[1]["status"] == "ok"


def test_error_exit_marks_status():
    tracer = obs.configure(enabled=True)
    with pytest.raises(RuntimeError):
        with tracer.span("boom"):
            raise RuntimeError("x")
    assert tracer.snapshot()[0]["status"] == "error"


def test_snapshot_while_recording_never_raises():
    """Readers (exemplar capture, /varz scrapes) snapshot the ring while
    worker threads record spans: a bare deque iteration would raise
    'deque mutated during iteration' — the ring lock must prevent it."""
    tracer = obs.configure(enabled=True, capacity=256)
    stop = threading.Event()
    errors = []

    def writer():
        try:
            while not stop.is_set():
                with tracer.span("w"):
                    pass
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    def reader():
        try:
            for _ in range(200):
                tracer.snapshot()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    writers = [threading.Thread(target=writer) for _ in range(3)]
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in writers + readers:
        t.start()
    for t in readers:
        t.join()
    stop.set()
    for t in writers:
        t.join()
    assert not errors, errors
    assert len(tracer.snapshot()) == 256  # ring stayed bounded


# -- engine / pipeline nesting ---------------------------------------------

def test_map_batches_trace_nests_pipeline_stages(model):
    """map_batches(pipeline=True): engine.dispatch nests under the
    pipeline.dispatch stage span, stages nest under pipeline.run, one
    dispatch/gather span per piece, and the gather spans carry the
    block_until_ready-bracketed device split."""
    from sparkdl_tpu.parallel.engine import InferenceEngine

    variables, x = model
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    obs.configure(enabled=True)
    list(eng.map_batches([x], pipeline=True))
    spans = obs.get_tracer().snapshot()
    names = [s["name"] for s in spans]
    n_pieces = 7  # ceil(50/8)
    assert names.count("pipeline.dispatch") == n_pieces
    assert names.count("pipeline.gather") == n_pieces
    assert names.count("pipeline.run") == 1
    assert names.count("engine.dispatch") == n_pieces
    assert _chains(spans, "engine.dispatch") == \
        [("engine.dispatch", "pipeline.dispatch", "pipeline.run")] * n_pieces
    assert _chains(spans, "pipeline.gather") == \
        [("pipeline.gather", "pipeline.run")] * n_pieces
    assert _assert_child_within_parent(spans) >= 3 * n_pieces
    gathers = [s for s in spans if s["name"] == "pipeline.gather"]
    assert all("device_us" in s for s in gathers)


def test_pipeline_outputs_identical_with_tracing_on(model):
    """Tracing must be an observer: pipelined outputs with tracing ON
    are byte-identical to the untraced run."""
    from sparkdl_tpu.parallel.engine import InferenceEngine

    variables, x = model
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    obs.configure(enabled=False)
    ref = list(eng.map_batches([x], pipeline=True))
    obs.configure(enabled=True)
    traced = list(eng.map_batches([x], pipeline=True))
    assert len(ref) == len(traced)
    for a, b in zip(ref, traced):
        np.testing.assert_array_equal(a, b)


def test_overlap_contract_holds_with_tracing_on():
    """The tier-1 >= 1.5x synthetic-slow-device contract must survive
    tracing ON (the run-tests.sh guard asserts the same plus the
    disabled-path factor)."""
    from sparkdl_tpu.parallel.pipeline import synthetic_overlap_benchmark

    obs.configure(enabled=True)
    result = synthetic_overlap_benchmark()
    assert result["speedup"] >= 1.5, result
    spans = obs.get_tracer().snapshot()
    assert any(s["name"] == "pipeline.run" for s in spans)


# -- THE acceptance test: end-to-end nesting + valid Chrome trace ----------

def test_end_to_end_trace_nesting_and_chrome_json(model, tmp_path):
    """CPU-backend end-to-end run (a serving request wave AND a
    map_batches call) -> valid Chrome-trace JSON whose spans nest
    serving.request -> serving.microbatch -> engine.call ->
    engine.dispatch and pipeline.run -> pipeline.<stage>, with
    non-overlapping child/parent window invariants throughout."""
    from sparkdl_tpu.parallel.engine import InferenceEngine
    from sparkdl_tpu.serving import Server

    variables, x = model
    obs.configure(enabled=True)

    # online: one wave of single-example requests
    with Server(_fn, dict(variables), max_batch_size=8,
                max_wait_ms=2.0) as srv:
        futs = [srv.submit(row) for row in x[:20]]
        for f in futs:
            f.result()
    # offline: one pipelined map_batches call
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    list(eng.map_batches([x], pipeline=True))

    tracer = obs.get_tracer()
    spans = tracer.snapshot()
    names = {s["name"] for s in spans}
    assert {"serving.request", "serving.microbatch", "engine.call",
            "engine.dispatch", "pipeline.run", "pipeline.dispatch",
            "pipeline.gather"} <= names
    # every request span is a trace ROOT; every microbatch adopts the
    # first live member's trace
    reqs = [s for s in spans if s["name"] == "serving.request"]
    assert len(reqs) == 20 and all(s["parent_id"] is None for s in reqs)
    req_traces = {s["trace_id"] for s in reqs}
    batches = [s for s in spans if s["name"] == "serving.microbatch"]
    assert batches and all(s["trace_id"] in req_traces for s in batches)
    assert all(s["attrs"]["batch_size"] >= 1 for s in batches)
    # the serving chain, leaf to root
    serving_chains = [c for c in _chains(spans, "engine.dispatch")
                      if "serving.microbatch" in c]
    assert serving_chains and all(
        c == ("engine.dispatch", "engine.call", "serving.microbatch",
              "serving.request") for c in serving_chains)
    assert _assert_child_within_parent(spans) >= len(serving_chains)

    # valid Chrome trace JSON: round-trips through disk, every complete
    # event has the required fields, and span lineage rides args
    path = tmp_path / "trace.json"
    obs.write_chrome_trace(str(path), spans)
    doc = json.loads(path.read_text())
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(events) == len(spans)
    for e in events:
        assert e["name"] and "ts" in e and "dur" in e and e["dur"] >= 0
        assert "trace_id" in e["args"] and "span_id" in e["args"]
    # and the artifact reloads as spans (the trace_summary input path)
    assert len(obs.load_spans(str(path))) == len(spans)


def test_shed_request_span_records_shed_status():
    from sparkdl_tpu.serving.batcher import DynamicBatcher, Request
    from sparkdl_tpu.serving.errors import DeadlineExceededError

    tracer = obs.configure(enabled=True)
    b = DynamicBatcher(max_batch_size=4, max_wait_ms=1.0)
    r = Request(np.zeros(3), deadline=-1.0)  # already expired
    r.span = tracer.start_span("serving.request")
    b.submit(r)
    batch = b.next_batch()
    assert batch == []
    with pytest.raises(DeadlineExceededError):
        r.future.result(timeout=1)
    spans = tracer.snapshot()
    assert [s["status"] for s in spans
            if s["name"] == "serving.request"] == ["shed"]


# -- exemplars + varz ------------------------------------------------------

def test_exemplar_reservoir_keeps_top_k():
    from sparkdl_tpu.obs.exemplar import ExemplarReservoir

    tracer = obs.configure(enabled=True)
    res = ExemplarReservoir(k=2)
    # admission is against the CURRENT floor: 0.02 evicts 0.01 when it
    # arrives; only the final 0.04 (floor already 0.05) is rejected
    for i, dur in enumerate([0.01, 0.05, 0.02, 0.30, 0.04]):
        with tracer.span("serving.request") as sp:
            tid = sp.trace_id
        assert res.offer(dur, tid, tracer) == (dur != 0.04)
    snap = res.snapshot()
    assert [e["duration_ms"] for e in snap] == [300.0, 50.0]
    assert all(e["spans"] for e in snap)  # full span tree captured
    # inert while tracing is disabled
    res2 = ExemplarReservoir(k=2)
    assert not res2.offer(9.9, "t1", obs.configure(enabled=False))
    assert res2.snapshot() == []


def test_server_varz_structured_form(model):
    from sparkdl_tpu.serving import Server

    variables, x = model
    obs.configure(enabled=True)
    with Server(_fn, dict(variables), max_batch_size=8,
                max_wait_ms=2.0) as srv:
        for f in [srv.submit(row) for row in x[:16]]:
            f.result()
        v = srv.varz()
    json.dumps(v)  # the monitoring endpoint body must serialize
    assert v["server"]["max_batch_size"] == 8
    assert v["counters"]["serving.requests"] == 16
    assert v["counters"]["serving.completed"] == 16
    assert v["latency_ms"]["request"]["p99_ms"] >= \
        v["latency_ms"]["request"]["p50_ms"] > 0
    assert v["metrics"]["counters"]["serving.batches"] >= 1
    assert v["exemplars"], "tracing was on: slow-request exemplars expected"
    ex = v["exemplars"][0]
    assert ex["duration_ms"] > 0 and ex["trace_id"]
    assert any(s["name"] == "serving.request" for s in ex["spans"])
    # flat stats() keeps working alongside the structured form
    assert srv.stats()["serving.requests"] == 16


# -- metrics satellites ----------------------------------------------------

def test_percentile_name_collision_is_deterministic():
    m = Metrics()
    m.observe("x", 5.0)             # histogram "x"
    m.timings_s.setdefault("x", [])  # EMPTY timing series, same name
    # timings win even when empty (the or-short-circuit used to fall
    # through to the histogram, flipping family with buffer occupancy)
    assert m.percentile("x", 50) is None
    assert m.percentile("x", 50, kind="histogram") == 5.0
    m.record_time("x", 2.0)
    assert m.percentile("x", 50) == 2.0
    assert m.percentile("x", 50, kind="timing") == 2.0
    assert m.percentile("x", 50, kind="histogram") == 5.0
    assert m.percentile("absent", 99) is None
    with pytest.raises(ValueError, match="kind"):
        m.percentile("x", 50, kind="bogus")


def test_metrics_concurrent_writers_no_lost_counts():
    """Admission thread + dispatch workers + pipeline stages hammer ONE
    registry: counters must be exact (no lost increments) and every
    series must stay within the max_samples bound."""
    m = Metrics(max_samples=256)
    n_threads, n_iters = 8, 2000
    barrier = threading.Barrier(n_threads)
    errors = []

    def hammer(tid):
        try:
            barrier.wait()
            for i in range(n_iters):
                m.incr("shared.count")
                m.incr(f"worker.{tid}", 2.0)
                m.record_time("shared.latency", i * 1e-6)
                m.observe("shared.depth", float(i % 7))
                m.gauge("shared.gauge", float(i))
                if i % 100 == 0:
                    m.percentile("shared.latency", 99)  # reader in the mix
                    m.summary()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert m.counters["shared.count"] == n_threads * n_iters
    for t in range(n_threads):
        assert m.counters[f"worker.{t}"] == 2.0 * n_iters
    raw = m.snapshot_raw()
    assert 0 < len(raw["timings_s"]["shared.latency"]) <= 256
    assert 0 < len(raw["histograms"]["shared.depth"]) <= 256
    json.dumps(obs.metrics_snapshot(m))  # snapshot stays serializable


# -- exporters -------------------------------------------------------------

def _seeded_metrics():
    m = Metrics()
    m.incr("serving.requests", 3)
    m.gauge("queue.depth", 2.0)
    for v in (0.010, 0.020, 0.030):
        m.record_time("request_latency", v)
    m.observe("fill-ratio", 0.5)
    return m


def test_metrics_snapshot_stable_schema():
    snap = obs.metrics_snapshot(_seeded_metrics())
    assert set(snap) == {"counters", "gauges", "timings_s", "histograms"}
    t = snap["timings_s"]["request_latency"]
    assert set(t) == {"count", "total_s", "mean_s", "p50_s", "p99_s"}
    assert t["count"] == 3 and t["p50_s"] == 0.02 and t["p99_s"] == 0.03
    h = snap["histograms"]["fill-ratio"]
    assert set(h) == {"count", "mean", "p50", "p99"}
    assert snap["counters"]["serving.requests"] == 3
    assert snap["gauges"]["queue.depth"] == 2.0


def test_prometheus_text_exposition():
    text = obs.prometheus_text(_seeded_metrics())
    assert "# TYPE sparkdl_serving_requests_total counter" in text
    assert "sparkdl_serving_requests_total 3" in text
    assert "# TYPE sparkdl_queue_depth gauge" in text
    assert "# TYPE sparkdl_request_latency_seconds summary" in text
    assert 'sparkdl_request_latency_seconds{quantile="0.99"} 0.03' in text
    assert "sparkdl_request_latency_seconds_count 3" in text
    assert "sparkdl_fill_ratio" in text  # '-' sanitized to '_'
    assert text.endswith("\n")


def test_metrics_jsonl_appends(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    m = _seeded_metrics()
    obs.write_metrics_jsonl(path, m, extra={"config": "a"})
    obs.write_metrics_jsonl(path, m, extra={"config": "b"})
    lines = [json.loads(line)
             for line in open(path).read().strip().splitlines()]
    assert [r["config"] for r in lines] == ["a", "b"]
    assert all(r["ts"] and r["counters"]["serving.requests"] == 3
               for r in lines)


def test_spans_jsonl_roundtrip(tmp_path):
    tracer = obs.configure(enabled=True)
    with tracer.span("a"):
        with tracer.span("b"):
            pass
    spans = tracer.snapshot()
    path = str(tmp_path / "spans.jsonl")
    obs.write_spans_jsonl(path, spans)
    assert obs.load_spans(path) == spans


def test_tracer_flush_writes_both_artifacts(tmp_path):
    tracer = obs.configure(enabled=True, out_dir=str(tmp_path / "td"))
    with tracer.span("a"):
        pass
    paths = tracer.flush()
    assert len(paths) == 2
    chrome = [p for p in paths if p.endswith(".json")][0]
    assert json.loads(open(chrome).read())["traceEvents"]
    jsonl = [p for p in paths if p.endswith(".jsonl")][0]
    assert obs.load_spans(jsonl)[0]["name"] == "a"
    # the DIRECTORY itself loads too — the trace_artifact shape bench
    # emits for subprocess configs folds without naming a file
    assert obs.load_spans(str(tmp_path / "td"))[0]["name"] == "a"
    # empty ring / no dir -> no files, no error
    tracer.clear()
    assert tracer.flush() == []


# -- trace-id-aware logs ---------------------------------------------------

def test_log_records_carry_current_trace_id():
    from sparkdl_tpu.utils.logging import _TraceContextFilter

    f = _TraceContextFilter()

    def record():
        return logging.LogRecord("sparkdl_tpu.x", logging.INFO, "f", 1,
                                 "msg", None, None)

    obs.configure(enabled=False)
    r = record()
    assert f.filter(r) and r.trace == ""
    tracer = obs.configure(enabled=True)
    with tracer.span("op") as sp:
        r = record()
        assert f.filter(r) and r.trace == f" trace={sp.trace_id}"
    r = record()
    assert f.filter(r) and r.trace == ""  # outside any span again


# -- trace_summary CLI -----------------------------------------------------

def test_trace_summary_cli_folds_both_forms(tmp_path):
    tracer = obs.configure(enabled=True)
    with tracer.span("pipeline.run"):
        for _ in range(3):
            with tracer.span("pipeline.prepare"):
                pass
    spans = tracer.snapshot()
    jsonl = str(tmp_path / "spans.jsonl")
    chrome = str(tmp_path / "trace.json")
    obs.write_spans_jsonl(jsonl, spans)
    obs.write_chrome_trace(chrome, spans)
    flushdir = str(tmp_path / "flushed")
    os.makedirs(flushdir)
    obs.write_spans_jsonl(os.path.join(flushdir, "spans_1.jsonl"), spans)
    tool = os.path.join(REPO, "tools", "trace_summary.py")
    for src, extra in ((jsonl, []),
                       (flushdir, []),  # directory-form trace_artifact
                       (chrome, ["--wall-span", "pipeline.run"])):
        out = subprocess.run(
            [sys.executable, tool, src, *extra],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "| stage |" in out.stdout
        assert "pipeline.prepare | 3 |" in out.stdout
        assert "wall:" in out.stdout


# -- bench integration -----------------------------------------------------

def test_bench_lines_carry_fresh_snapshot_and_trace_artifact(tmp_path,
                                                             monkeypatch):
    """Driver-record contract: each per-config line carries THAT
    config's metrics snapshot (fresh registry — no accumulation from
    earlier configs) and a trace artifact path that exists and loads."""
    import bench

    lines = []
    monkeypatch.setattr(bench, "_print_line",
                        lambda s: lines.append(json.loads(s)))
    monkeypatch.setattr(bench, "_LINES", {})
    monkeypatch.setattr(bench, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(bench, "BENCH_TRACE", True)
    # the fake configs stand in for chip configs; tier-1 has no chip
    monkeypatch.setattr(bench, "require_accelerator", bench.device_stamp)

    def fake_config(key):
        def run():
            m = bench._config_metrics()
            m.incr(f"{key}.work")
            with obs.get_tracer().span(f"{key}.stage"):
                pass
            bench.emit(key, "fake metric", 1.0, "units")
        return run

    monkeypatch.setitem(bench.BENCHES, "fakeA", fake_config("fakeA"))
    monkeypatch.setitem(bench.BENCHES, "fakeB", fake_config("fakeB"))
    monkeypatch.setenv("SPARKDL_BENCH_CONFIGS", "fakeA,fakeB")
    assert bench.main() == 0

    by_config = {r["config"]: r for r in lines if "metric" in r}
    for key, other in (("fakeA", "fakeB"), ("fakeB", "fakeA")):
        rec = by_config[key]
        snap = rec["metrics_snapshot"]
        assert snap["counters"] == {f"{key}.work": 1.0}, \
            f"{other} leaked into {key}'s snapshot"
        path = rec["trace_artifact"]
        assert path.endswith(f"trace_{key}.json")
        assert os.path.exists(path)
        loaded = obs.load_spans(path)
        assert [s["name"] for s in loaded] == [f"{key}.stage"]
    # main() restored the env-configured tracer (disabled in tests)
    assert not obs.get_tracer().enabled
