"""Compiles seen from inside the program (ISSUE 39): the listener of
``parallel.compile_cache`` turns ``jax.monitoring``'s compile phases into
closed spans ``compile.trace`` / ``compile.lower`` / ``compile.backend``
under the span that caused them, and into seconds in ``stats()`` whether
the tracer is on or not; ``Tracer.record`` is the span closed at birth
they are made of; ``engine.build`` is the engine's own part of set-up."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from sparkdl_tpu import obs
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.obs.trace import NULL_SPAN, Tracer
from sparkdl_tpu.parallel import compile_cache
from sparkdl_tpu.parallel.engine import (InferenceEngine,
                                         clear_engine_jit_cache,
                                         get_cached_engine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("compile.trace", "compile.lower", "compile.backend")
SECONDS = ("trace_s", "lower_s", "backend_s")
BATCH = 8
X = np.linspace(-1.0, 1.0, 3 * BATCH * 4, dtype=np.float32).reshape(-1, 4)


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    # nothing placed, nothing switched on: the library's default, the
    # persistent cache off
    monkeypatch.delenv(compile_cache.PLACED_DIR_ENV, raising=False)
    monkeypatch.delenv("SPARKDL_COMPILE_CACHE", raising=False)
    compile_cache._reset_for_tests()
    clear_engine_jit_cache()
    yield
    compile_cache._reset_for_tests()
    clear_engine_jit_cache()
    obs.configure_from_env()


def _program():
    """A program no other test has compiled (a new function object a
    call), with a jitted function inside it."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * 2.0

    def spans_probe(v, x):
        return inner(x @ v["w"]) + inner(x @ v["w"] + 1.0)

    return spans_probe, {"w": np.ones((4, 3), np.float32)}


def _engine():
    fn, variables = _program()
    return InferenceEngine(fn, variables, device_batch_size=BATCH)


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _end(span):
    return span["ts_us"] + span["dur_us"]


@pytest.mark.parametrize("pipeline,above", [
    (True, ("engine.dispatch", "pipeline.dispatch", "pipeline.run",
            "engine.call")),
    (False, ("engine.dispatch", "engine.call")),
], ids=["pipelined", "direct"])
def test_first_dispatch_leaves_one_span_a_phase_under_it(pipeline, above):
    tracer = obs.configure(enabled=True)
    engine = _engine()
    tracer.clear()
    engine(X, pipeline=pipeline)
    spans = tracer.snapshot()
    by_id = {s["span_id"]: s for s in spans}
    first = min(_named(spans, "engine.dispatch"), key=lambda s: s["ts_us"])
    under = [s for s in spans if s["parent_id"] == first["span_id"]
             and s["name"] in PHASES]
    # one a phase, in the order they happen: the jitted function inside
    # the program was traced under it and left no span of its own
    assert [s["name"] for s in under] == list(PHASES)
    assert all(s["attrs"]["program"] == "spans_probe" for s in under)
    for s in under:
        assert first["ts_us"] <= s["ts_us"] and _end(s) <= _end(first)
        assert s["trace_id"] == first["trace_id"]
    for a, b in zip(under, under[1:]):
        assert _end(a) <= b["ts_us"] + 1.0          # microseconds
    chain, span = [], first
    while span is not None:
        chain.append(span["name"])
        span = by_id.get(span["parent_id"])
    assert tuple(chain) == above
    # the later dispatches of the call compiled nothing
    assert len(_named(spans, "engine.dispatch")) == 3
    assert len([s for s in spans if s["name"] in PHASES
                and s["attrs"]["program"] == "spans_probe"]) == 3


def test_second_call_leaves_no_compile_span():
    tracer = obs.configure(enabled=True)
    engine = _engine()
    engine(X)
    before = compile_cache.stats()
    tracer.clear()
    engine(X)
    assert [s for s in tracer.snapshot() if s["name"] in PHASES] == []
    assert compile_cache.stats() == before


def test_cache_reads_off_where_the_persistent_cache_is_disabled():
    tracer = obs.configure(enabled=True)
    _engine()(X)
    assert compile_cache.state() is None
    backends = _named(tracer.snapshot(), "compile.backend")
    assert backends
    assert all(s["attrs"]["cache"] == "off" for s in backends)
    assert all("load_s" not in s["attrs"] for s in backends)
    stats = compile_cache.stats()
    assert stats["hits"] == stats["misses"] == 0
    assert stats["backend_s"] > 0.0 and stats["load_s"] == 0.0


_CHILD = """
import hashlib, json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
from sparkdl_tpu import obs
from sparkdl_tpu.parallel import compile_cache
from sparkdl_tpu.parallel.engine import InferenceEngine

def restart_probe(v, x):
    import jax.numpy as jnp
    return jnp.tanh(x * v["s"] + 0.25)

tracer = obs.configure(enabled=True)
engine = InferenceEngine(restart_probe, {{"s": np.float32(3.0)}},
                         device_batch_size=8)
out = engine(np.linspace(0, 1, 48, dtype=np.float32).reshape(8, 6))
mine = [s["attrs"] for s in tracer.snapshot()
        if s["name"] == "compile.backend"
        and s["attrs"]["program"] == "restart_probe"]
print(json.dumps({{"backend": mine, "stats": compile_cache.stats(),
                   "digest": hashlib.sha256(out.tobytes()).hexdigest()}}))
"""


def _run_restart(cache_dir):
    env = dict(os.environ)
    env.pop(compile_cache.PLACED_DIR_ENV, None)
    env.pop("SPARKDL_FAULTS", None)
    env.pop("SPARKDL_TRACE", None)
    env["SPARKDL_COMPILE_CACHE"] = str(cache_dir)
    r = subprocess.run([sys.executable, "-c", _CHILD.format(repo=REPO)],
                       capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_reads_miss_then_hit_across_a_restart(tmp_path):
    a = _run_restart(tmp_path / "cc")
    assert [b["cache"] for b in a["backend"]] == ["miss"]
    assert "load_s" not in a["backend"][0]
    assert a["stats"]["misses"] > 0 and a["stats"]["load_s"] == 0.0
    b = _run_restart(tmp_path / "cc")
    assert [x["cache"] for x in b["backend"]] == ["hit"]
    assert b["backend"][0]["load_s"] > 0.0
    assert "saved_s" in b["backend"][0]
    assert b["stats"]["misses"] == 0
    assert b["stats"]["load_s"] >= b["backend"][0]["load_s"]
    # tracing and lowering are paid on the warm cache too
    assert b["stats"]["trace_s"] > 0.0 and b["stats"]["lower_s"] > 0.0
    assert b["digest"] == a["digest"]


def test_tracer_off_no_span_seconds_move_results_identical(monkeypatch):
    fn, variables = _program()
    made = []
    real_make = Tracer._make
    monkeypatch.setattr(
        Tracer, "_make",
        lambda self, *a: made.append(a[0]) or real_make(self, *a))
    tracer = obs.configure(enabled=False)
    assert tracer.record("compile.trace", 0.5, program="f") is NULL_SPAN
    before = compile_cache.stats()
    off = InferenceEngine(fn, variables, device_batch_size=BATCH)(X)
    moved = compile_cache.stats()
    assert made == [] and len(tracer) == 0      # no span object anywhere
    assert all(moved[k] > before[k] for k in SECONDS)

    fn, variables = _program()                  # the same program, anew
    tracer = obs.configure(enabled=True)
    on = InferenceEngine(fn, variables, device_batch_size=BATCH)(X)
    assert "compile.backend" in made and "engine.build" in made
    assert np.array_equal(off, on)
    assert off.dtype == on.dtype


def test_a_phase_inside_another_counts_once():
    """JAX reports a trace for every jitted function it meets while it
    traces the program: their seconds lie inside the program's own."""
    import jax
    import jax.numpy as jnp

    tracer = obs.configure(enabled=True)
    compile_cache.ensure_from_env()

    @jax.jit
    def leaf(x):
        return jnp.sin(x) + jnp.cos(x)

    @jax.jit
    def whole_program(x):
        return leaf(x) * leaf(x + 1.0) + jnp.sum(x)

    x = jnp.ones((4, 4))            # its own small compile, before
    before = compile_cache.stats()
    whole_program(x).block_until_ready()
    mine = [s for s in tracer.snapshot() if s["name"] in PHASES
            and s["attrs"]["program"] == "whole_program"]
    assert [s["name"] for s in mine] == list(PHASES)
    assert not [s for s in tracer.snapshot() if s["name"] in PHASES
                and s["attrs"]["program"] in ("leaf", "sin", "cos")]
    assert all(s["parent_id"] is None for s in mine)   # the caller's own jit
    after = compile_cache.stats()
    for span, key in zip(mine, SECONDS):
        assert after[key] - before[key] == pytest.approx(
            span["dur_us"] / 1e6, abs=1e-4)


def test_compile_on_another_thread_is_parented_there():
    import jax
    import jax.numpy as jnp

    tracer = obs.configure(enabled=True)
    compile_cache.ensure_from_env()

    def work():
        with tracer.span("engine.call", rows=1):
            jax.jit(lambda x: jnp.exp(x) - 1.0)(
                jnp.ones((3,))).block_until_ready()

    with tracer.span("transform.run"):
        t = threading.Thread(target=work)
        t.start()
        t.join()
    spans = tracer.snapshot()
    call = _named(spans, "engine.call")[0]
    backends = _named(spans, "compile.backend")
    assert backends and all(s["parent_id"] == call["span_id"]
                            and s["tid"] == call["tid"] for s in backends)
    assert call["parent_id"] is None            # a thread of its own


def test_compiles_on_many_threads_lose_no_second():
    """More threads than cores report phases at once (``jax.monitoring``'s
    own calls, as JAX makes them: a scalar at a phase's start, the
    duration at its end, a nested phase inside): every outermost second
    is counted once, the depth is each thread's own."""
    import jax.monitoring as monitoring

    tracer = obs.configure(enabled=True, capacity=1 << 15)
    compile_cache.ensure_from_env()
    trace_event, = [k for k, v in compile_cache._PHASES.items()
                    if v == "trace"]
    threads, each = 24, 200
    before = compile_cache.stats()["trace_s"]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        for _ in range(each):
            monitoring.record_scalar(trace_event, 0.0, fun_name="outer")
            monitoring.record_scalar(trace_event, 0.0, fun_name="inner")
            monitoring.record_event_duration_secs(trace_event, 0.5,
                                                  fun_name="inner")
            monitoring.record_event_duration_secs(trace_event, 0.25,
                                                  fun_name="outer")

    try:
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(switch)
    assert compile_cache.stats()["trace_s"] - before == threads * each * 0.25
    mine = _named(tracer.snapshot(), "compile.trace")
    assert len(mine) == threads * each and tracer.dropped == 0
    assert {s["attrs"]["program"] for s in mine} == {"outer"}


# -- Tracer.record ----------------------------------------------------------

def test_record_began_seconds_ago_and_ended_now():
    import time

    tracer = Tracer(enabled=True)
    t_before = time.perf_counter()
    span = tracer.record("compile.lower", 0.25, program="f", cache="off")
    t_after = time.perf_counter()
    assert span.t0 == span.t1 - 0.25
    assert t_before <= span.t1 <= t_after
    assert span.attrs == {"program": "f", "cache": "off"}
    assert tracer.current() is None             # pushed on no stack
    (d,) = tracer.snapshot()
    assert d["name"] == "compile.lower" and d["status"] == "ok"
    assert d["dur_us"] == pytest.approx(0.25e6, abs=1.0)
    assert span.finish("error") is span and len(tracer) == 1   # closed


def test_record_parent_rules():
    tracer = Tracer(enabled=True)
    root = tracer.record("compile.trace", 0.1)
    assert root.parent_id is None and root.trace_id
    with tracer.span("engine.dispatch") as outer:
        under = tracer.record("compile.trace", 0.1)
        other = tracer.start_span("serving.request")
        explicit = tracer.record("compile.trace", 0.1, parent=other)
        other.finish()
    assert (under.parent_id, under.trace_id) == (outer.span_id,
                                                 outer.trace_id)
    assert (explicit.parent_id, explicit.trace_id) == (other.span_id,
                                                       other.trace_id)
    assert root.trace_id not in (outer.trace_id, other.trace_id)
    assert Tracer(enabled=False).record("compile.trace", 0.1) is NULL_SPAN


def test_record_into_a_full_ring_is_counted_by_dropped():
    tracer = Tracer(enabled=True, capacity=2)
    for _ in range(2):
        tracer.record("compile.trace", 0.1)
    assert tracer.dropped == 0
    tracer.record("compile.lower", 0.1)
    assert tracer.dropped == 1 and len(tracer) == 2
    assert [s["name"] for s in tracer.snapshot()] == ["compile.trace",
                                                      "compile.lower"]


# -- engine.build -----------------------------------------------------------

def test_engine_build_once_an_engine_none_on_a_cached_hit():
    tracer = obs.configure(enabled=True)
    fn, variables = _program()
    mf = ModelFunction(fn, variables)

    class Holder:
        pass

    holder = Holder()
    first = get_cached_engine(holder, mf, device_batch_size=BATCH)
    assert get_cached_engine(holder, mf, device_batch_size=BATCH) is first
    (build,) = _named(tracer.snapshot(), "engine.build")
    assert build["attrs"] == {"device_batch_size": BATCH, "jit_cached": False,
                              "param_bytes": 4 * 3 * 4}
    assert build["parent_id"] is None
    # a second engine over the same function: its own span, the jit found
    InferenceEngine(fn, variables, device_batch_size=BATCH)
    builds = _named(tracer.snapshot(), "engine.build")
    assert [b["attrs"]["jit_cached"] for b in builds] == [False, True]


def test_a_compile_during_build_is_parented_under_it():
    """The cast to the compute dtype runs on the device, inside
    ``engine.build``: what it compiles is the build's, by parent."""
    import jax.numpy as jnp

    tracer = obs.configure(enabled=True)
    compile_cache.ensure_from_env()             # the listener, before
    fn, _ = _program()
    weights = {"w": jnp.arange(35.0, dtype=jnp.float32).reshape(5, 7)}
    InferenceEngine(fn, weights, device_batch_size=BATCH,
                    compute_dtype=jnp.bfloat16)
    spans = tracer.snapshot()
    (build,) = _named(spans, "engine.build")
    under = [s for s in spans if s["parent_id"] == build["span_id"]]
    assert under and {s["name"] for s in under} <= set(PHASES)
    for s in under:
        assert build["ts_us"] <= s["ts_us"] and _end(s) <= _end(build) + 1.0
