"""Chaos suite: deterministic fault injection across the scoring stack.

Tier-1 (CPU-only, 8-device virtual mesh).  Pins ISSUE 4's failure-domain
contracts with the :mod:`sparkdl_tpu.faults` harness:

* the spec grammar / plan semantics (seeded determinism, at/every/p/
  times schedules, sticky ``dead``);
* engine dispatch retry (jittered, capped) + circuit breaker
  (fail-fast ``CircuitOpenError``, half-open recovery);
* pipeline worker crashes -> structured ``PipelineStageError`` with the
  failing stage + piece, clean drain (no wedged threads/queues);
* serving: queue-full storms, breaker-open shed with ``retry_after``,
  ``health()`` ready/degraded/closed transitions, wedged-model drain;
* host I/O decode errors ride the drop-to-null contract;
* the chaos e2e acceptance run and the kill-the-driver bench-artifact
  test (SIGKILL mid-run -> valid JSONL for every completed config).
"""

import json
import os
import random
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from sparkdl_tpu import faults
from sparkdl_tpu.faults import FaultPlan
from sparkdl_tpu.parallel.engine import CircuitOpenError, InferenceEngine
from sparkdl_tpu.parallel.pipeline import PipelineStageError
from sparkdl_tpu.serving import (QueueFullError, Server,
                                 ServiceUnavailableError)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolated_plan():
    """Never leak a plan between tests (or out of the suite)."""
    from sparkdl_tpu.faults import plan as plan_mod

    prev = plan_mod._PLAN
    yield
    plan_mod._PLAN = prev


def _fn(variables, x):
    import jax.numpy as jnp

    return jnp.tanh(x @ variables["w"])


@pytest.fixture(scope="module")
def model():
    rng = np.random.default_rng(4)
    variables = {"w": rng.normal(size=(6, 4)).astype(np.float32)}
    x = rng.normal(size=(24, 6)).astype(np.float32)
    return variables, x


def _no_stack_threads(prefixes=("sparkdl-pipeline", "sparkdl-serving"),
                      timeout_s=5.0):
    """Join-with-timeout assert: every stack worker thread exits."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        left = [t.name for t in threading.enumerate()
                if t.name.startswith(prefixes)]
        if not left:
            return
        time.sleep(0.02)
    raise AssertionError(f"wedged threads after {timeout_s}s: {left}")


# -- spec grammar / plan semantics -----------------------------------------

def test_spec_parse_roundtrip_and_rejects():
    spec = ("seed=7;engine.dispatch:error:exc=transient,at=2;"
            "serving.admit:error:exc=queue_full,times=3;"
            "pipeline.gather:sleep:every=2,ms=1")
    plan = FaultPlan.parse(spec)
    assert plan.seed == 7
    assert plan.sites() == {"engine.dispatch", "serving.admit",
                            "pipeline.gather"}
    assert FaultPlan.parse(plan.spec).spec == plan.spec  # canonical form
    for bad in ("nope.site:error", "engine.dispatch:boom",
                "engine.dispatch:error:zz=1", "seed=x",
                "engine.dispatch:error:exc=nonsense", "justasite",
                # queue_full is not an InjectedFault: outside serving.*
                # it would escape the site handlers instead of testing
                # them, so the grammar refuses it there
                "io.decode:error:exc=queue_full",
                "engine.dispatch:error:exc=queue_full"):
        with pytest.raises(ValueError):
            faults.parse_spec(bad)
    # a seed embedded in a rule STRING means the same as in parse()
    p = FaultPlan(["seed=9;engine.dispatch:error:p=0.5"])
    assert p.seed == 9 and p.spec.startswith("seed=9;")


def test_plan_schedules_fire_deterministically():
    # at= fires on exactly the Nth site call; times= caps firings
    plan = FaultPlan.parse("engine.dispatch:error:at=2")
    faults.configure(plan)
    faults.inject("engine.dispatch")
    with pytest.raises(faults.InjectedTransientError) as ei:
        faults.inject("engine.dispatch")
    assert ei.value.site == "engine.dispatch"
    faults.inject("engine.dispatch")  # inert again
    assert plan.fired() == 1 and plan.stats()["engine.dispatch"][
        "calls"] == 3

    # p= draws ride the per-rule seeded RNG: identical replay per seed
    def firing_seq(p):
        out = []
        for _ in range(30):
            try:
                p.fire("engine.dispatch", {})
                out.append(0)
            except faults.InjectedFault:
                out.append(1)
        return out

    s1 = firing_seq(FaultPlan.parse("seed=3;engine.dispatch:error:p=0.4"))
    s2 = firing_seq(FaultPlan.parse("seed=3;engine.dispatch:error:p=0.4"))
    s3 = firing_seq(FaultPlan.parse("seed=4;engine.dispatch:error:p=0.4"))
    assert s1 == s2 and 0 < sum(s1) < 30
    assert s1 != s3  # a different seed is a different chaos run


def test_dead_rule_is_sticky():
    faults.configure(FaultPlan.parse("engine.dispatch:dead:at=2"))
    faults.inject("engine.dispatch")
    for _ in range(3):  # once fired, EVERY later call keeps failing
        with pytest.raises(faults.InjectedDeadDeviceError):
            faults.inject("engine.dispatch")
    faults.clear()
    faults.inject("engine.dispatch")  # cleared: site is healthy again


def test_disabled_inject_is_noop_and_env_gate(monkeypatch):
    faults.clear()
    assert faults.inject("engine.dispatch") is None
    assert faults.get_plan() is None and faults.current_spec() is None
    monkeypatch.setenv("SPARKDL_FAULTS", "seed=5;io.decode:error:at=1")
    plan = faults.configure_from_env()
    assert plan is not None and plan.seed == 5
    assert faults.current_spec() == plan.spec
    with pytest.raises(faults.InjectedTransientError):
        faults.inject("io.decode")
    faults.clear()


def test_active_context_restores_previous_plan():
    outer = faults.configure(FaultPlan.parse("io.decode:error:at=1"))
    with faults.active(FaultPlan.parse("engine.dispatch:error:at=1")) as p:
        with pytest.raises(faults.InjectedFault):
            faults.inject("engine.dispatch")
        assert p.fired() == 1
    assert faults.get_plan() is outer
    faults.clear()


# -- retry satellite: jitter + bounded backoff -----------------------------

def test_backoff_delay_jittered_and_hard_capped():
    from sparkdl_tpu.utils.retry import backoff_delay

    rng = random.Random(0)
    # the cap binds AFTER jitter: no draw may exceed max_backoff_seconds
    for attempt in range(16):
        d = backoff_delay(attempt, 0.1, max_backoff_seconds=0.75,
                          jitter=0.5, rng=rng)
        assert 0.0 <= d <= 0.75
    # unjittered growth is the documented exponential below the cap
    assert backoff_delay(3, 0.1) == pytest.approx(0.8)
    assert backoff_delay(10, 0.1, max_backoff_seconds=2.0) == 2.0
    # jitter only DE-synchronizes (scales into [1-j, 1]), never inflates
    draws = {backoff_delay(2, 0.1, jitter=0.5, rng=random.Random(i))
             for i in range(20)}
    assert len(draws) > 5
    assert all(0.4 * 0.5 <= d <= 0.4 for d in draws)


def test_with_retries_sleeps_are_bounded(monkeypatch):
    from sparkdl_tpu.utils import retry as retry_mod

    sleeps = []
    monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)
    with pytest.raises(RuntimeError):
        retry_mod.with_retries(
            lambda: (_ for _ in ()).throw(RuntimeError("flaky")),
            max_retries=6, backoff_seconds=0.5,
            max_backoff_seconds=1.25, jitter=0.3)
    assert len(sleeps) == 6
    assert all(0.0 <= s <= 1.25 for s in sleeps), sleeps
    # without the cap, attempt 5 would have slept 0.5 * 2**5 = 16s
    assert max(sleeps) <= 1.25


# -- engine: dispatch retry + circuit breaker ------------------------------

def test_engine_retry_absorbs_transient_dispatch_fault(model):
    variables, x = model
    eng = InferenceEngine(_fn, variables, device_batch_size=8,
                          dispatch_retries=2, dispatch_backoff_s=0.001)
    ref = [np.asarray(o) for o in eng.map_batches([x], pipeline=False)]
    with faults.active(FaultPlan.parse(
            "engine.dispatch:error:exc=transient,at=2")) as plan:
        out = [np.asarray(o) for o in eng.map_batches([x], pipeline=False)]
        assert plan.fired("engine.dispatch") == 1
    assert all(np.array_equal(a, b) for a, b in zip(ref, out))
    assert eng.metrics.counters["engine.dispatch_retries"] == 1
    assert eng.breaker_state()["state"] == "closed"


def test_engine_fatal_faults_are_not_retried(model):
    variables, x = model
    eng = InferenceEngine(_fn, variables, device_batch_size=8,
                          dispatch_retries=3, dispatch_backoff_s=0.001)
    with faults.active(FaultPlan.parse(
            "engine.dispatch:error:exc=fatal,at=1")):
        with pytest.raises(faults.InjectedFatalError):
            list(eng.map_batches([x], pipeline=False))
    # deterministic failure: no retry burned, breaker not charged
    assert "engine.dispatch_retries" not in eng.metrics.counters
    assert eng.breaker_state()["consecutive_failures"] == 0


def test_breaker_opens_fails_fast_and_recovers(model):
    variables, x = model
    xb = x[:8]  # single device batch: the serial fast path, so the
    # injected error type reaches the caller unwrapped
    eng = InferenceEngine(_fn, variables, device_batch_size=8,
                          breaker_threshold=2, breaker_cooldown_s=0.25)
    eng(xb)  # healthy warm call
    with faults.active(FaultPlan.parse("engine.dispatch:dead:at=1")):
        for _ in range(2):  # two consecutive device errors trip it
            with pytest.raises(faults.InjectedDeadDeviceError):
                eng(xb)
        st = eng.breaker_state()
        assert st["state"] == "open" and st["consecutive_failures"] == 2
        assert "InjectedDeadDeviceError" in st["last_error"]
        # open = FAIL FAST: no dispatch attempt, a clear error, instantly
        t0 = time.perf_counter()
        with pytest.raises(CircuitOpenError) as ei:
            eng(xb)
        assert time.perf_counter() - t0 < 0.1
        assert ei.value.retry_after_s > 0
    time.sleep(0.3)  # cool-down elapses -> half-open admits one trial
    assert eng.breaker_state()["state"] == "half_open"
    # a DETERMINISTIC error during the trial proves nothing about the
    # device: the trial slot must be handed back (not pinned forever)
    with faults.active(FaultPlan.parse("engine.dispatch:error:exc=fatal")):
        with pytest.raises(faults.InjectedFatalError):
            eng(xb)
    assert eng.breaker_state()["state"] == "half_open"  # still probeable
    out = eng(xb)  # plan inactive: the trial succeeds and closes it
    assert eng.breaker_state()["state"] == "closed"
    assert np.asarray(out).shape == (len(xb), 4)


def test_force_time_device_errors_trip_breaker(model):
    """jax dispatch is async: a dying device usually raises when the
    result is FORCED (D2H), not at enqueue.  The engine.gather site
    proves those failures charge the same breaker — without this, a
    dead device would never trip fail-fast on real hardware."""
    variables, x = model
    xb = x[:8]
    eng = InferenceEngine(_fn, variables, device_batch_size=8,
                          breaker_threshold=2, breaker_cooldown_s=30.0)
    eng(xb)
    with faults.active(FaultPlan.parse("engine.gather:dead:at=1")):
        for _ in range(2):
            with pytest.raises(faults.InjectedDeadDeviceError):
                eng(xb)
        assert eng.breaker_state()["state"] == "open"
        with pytest.raises(CircuitOpenError):  # next DISPATCH fails fast
            eng(xb)
    assert eng.metrics.counters["engine.gather_errors"] == 2


# -- pipeline: structured stage crashes + clean drain ----------------------

@pytest.mark.parametrize("stage,at", [("gather", 2), ("dispatch", 1)])
def test_pipeline_stage_crash_is_structured_and_drains(model, stage, at):
    variables, x = model
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    batches = [x[i:i + 8] for i in range(0, len(x), 8)]
    ref = [np.asarray(o) for o in eng.map_batches(list(batches),
                                                  pipeline=False)]
    with faults.active(FaultPlan.parse(
            f"pipeline.{stage}:error:exc=transient,at={at},times=1")):
        with pytest.raises(PipelineStageError) as ei:
            list(eng.map_batches(list(batches), pipeline=True))
        assert ei.value.stage == stage
        assert ei.value.piece == at - 1  # 0-based failing piece index
        assert isinstance(ei.value.__cause__,
                          faults.InjectedTransientError)
        _no_stack_threads()  # crash drained the graph: nothing wedged
        # rule exhausted (times=1): the retried run completes, and is
        # bit-identical to the serial path
        out = [np.asarray(o) for o in eng.map_batches(list(batches),
                                                      pipeline=True)]
    assert all(np.array_equal(a, b) for a, b in zip(ref, out))
    assert eng.metrics.counters[f"pipeline.{stage}_crashes"] == 1
    _no_stack_threads()


def test_pipeline_fatal_cause_stays_non_retryable(model):
    """A deterministic failure inside a stage must surface as the
    ValueError-lineage PipelineStageFatalError, so utils.retry wrappers
    around the pipelined path still fail fast instead of re-burning a
    retry budget on a caller bug."""
    from sparkdl_tpu.parallel.pipeline import PipelineStageFatalError
    from sparkdl_tpu.utils.retry import NON_RETRYABLE, with_retries

    variables, x = model
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    batches = [x[i:i + 8] for i in range(0, len(x), 8)]
    calls = {"n": 0}

    def run_once():
        calls["n"] += 1
        with faults.active(FaultPlan.parse(
                "pipeline.gather:error:exc=fatal,at=1")):
            return list(eng.map_batches(list(batches), pipeline=True))

    with pytest.raises(PipelineStageFatalError) as ei:
        with_retries(run_once, max_retries=3)
    assert isinstance(ei.value, PipelineStageError)  # still the one type
    assert isinstance(ei.value, NON_RETRYABLE)
    assert calls["n"] == 1  # deterministic: zero retries burned
    _no_stack_threads()


def test_circuit_open_passes_through_pipeline_unwrapped(model):
    """The breaker's typed fail-fast signal must survive the pipelined
    path: wrapping CircuitOpenError in a RuntimeError-lineage
    PipelineStageError would strip retry_after_s/last_error and turn
    fail-fast back into retryable noise for utils.retry callers."""
    variables, x = model
    eng = InferenceEngine(_fn, variables, device_batch_size=8,
                          breaker_threshold=1, breaker_cooldown_s=30.0)
    batches = [x[i:i + 8] for i in range(0, len(x), 8)]
    with faults.active(FaultPlan.parse("engine.dispatch:dead:at=1")):
        with pytest.raises(PipelineStageError):  # the outage itself
            list(eng.map_batches(list(batches), pipeline=True))
        assert eng.breaker_state()["state"] == "open"
        with pytest.raises(CircuitOpenError) as ei:  # NOT wrapped
            list(eng.map_batches(list(batches), pipeline=True))
        assert ei.value.retry_after_s > 0
    _no_stack_threads()


def test_pipeline_prepare_crash_names_the_input_side(model):
    variables, x = model
    eng = InferenceEngine(_fn, variables, device_batch_size=8)

    def bad_batches():
        yield x[:8]
        raise OSError("decoder disk vanished")

    with pytest.raises(PipelineStageError) as ei:
        list(eng.map_batches(bad_batches(), pipeline=True))
    assert ei.value.stage == "prepare"
    assert isinstance(ei.value.__cause__, OSError)
    assert "decoder disk vanished" in str(ei.value)  # match= compat
    _no_stack_threads()


# -- serving: storms, breaker shed, health, wedged drain -------------------

def test_breaker_open_sheds_at_submit_with_retry_after(model):
    variables, x = model
    with Server(_fn, variables, max_batch_size=8, max_wait_ms=2,
                bucket_sizes=[8], breaker_threshold=1,
                breaker_cooldown_s=30.0) as srv:
        srv.predict(x[0])  # healthy
        with faults.active(FaultPlan.parse("engine.dispatch:dead:at=1")):
            with pytest.raises(faults.InjectedDeadDeviceError):
                srv.predict(x[1])  # trips the 1-failure breaker
            with pytest.raises(ServiceUnavailableError) as ei:
                srv.submit(x[2])  # shed at SUBMIT: no queue, no timeout
            assert ei.value.retry_after_s > 0
            h = srv.health()
            assert h["state"] == "degraded" and h["live"]
            assert h["breaker"][8]["state"] == "open"
            assert h["last_error"]["type"] == "InjectedDeadDeviceError"
            assert srv.metrics.counters["serving.rejected_breaker_open"] \
                == 1
    assert srv.health()["state"] == "closed"


def test_circuit_open_is_exempt_from_serving_retry_budget(model):
    """A batch whose dispatch hits an OPEN breaker must fail fast even
    with a server retry budget configured — retrying CircuitOpenError
    with backoff would turn every shed batch into seconds of dead sleep
    against a device known to be failing."""
    variables, x = model
    with Server(_fn, variables, max_batch_size=4, max_wait_ms=2,
                bucket_sizes=[4], max_retries=3, retry_backoff_s=0.4,
                breaker_threshold=1, breaker_cooldown_s=30.0) as srv:
        srv.predict(x[0])  # compile + healthy
        with faults.active(FaultPlan.parse("engine.dispatch:dead:at=1")):
            t0 = time.monotonic()
            with pytest.raises((faults.InjectedDeadDeviceError,
                                CircuitOpenError)):
                # attempt 1 dies (opens the 1-failure breaker); attempt 2
                # gates on CircuitOpenError and must NOT burn attempts
                # 3/4 with 0.8s/1.6s backoffs
                srv.predict(x[1])
            assert time.monotonic() - t0 < 1.5
    assert srv.metrics.counters.get("serving.batch_failures", 0) == 1


def test_close_drain_returns_within_timeout_with_wedged_model(model):
    """Satellite: ``close(drain=True, timeout_s=...)`` under an injected
    stalled model — queued requests settle with errors and the call
    returns within (a small multiple of) the timeout, not the wedge."""
    variables, x = model
    srv = Server(_fn, variables, max_batch_size=2, max_wait_ms=10,
                 bucket_sizes=[2], max_inflight_batches=1)
    try:
        srv.predict(x[0])  # compile outside the wedge window
        with faults.active(FaultPlan.parse(
                "serving.model:sleep:ms=2500,times=1")):
            wedged = [srv.submit(x[i]) for i in range(2)]
            time.sleep(0.3)  # let the wedged batch start its model call
            parked = [srv.submit(x[i]) for i in range(2, 4)]
            t0 = time.monotonic()
            srv.close(drain=True, timeout_s=0.5)
            elapsed = time.monotonic() - t0
            assert elapsed < 2.0, (
                f"close() took {elapsed:.2f}s — it waited out the wedge "
                f"instead of honoring timeout_s")
            from sparkdl_tpu.serving import ServerClosedError

            for f in parked:  # queued behind the wedge: settled, errored
                with pytest.raises(ServerClosedError):
                    f.result(timeout=10)
            # the wedged batch itself settles once its model call returns
            for f in wedged:
                np.asarray(f.result(timeout=30))
    finally:
        srv.close()
    _no_stack_threads(("sparkdl-serving",))


# -- host I/O site ---------------------------------------------------------

def test_io_decode_fault_rides_drop_to_null(fixture_images):
    from sparkdl_tpu.image.io import decodeResizeBatch

    blobs = []
    for p in fixture_images["paths"][:3]:
        with open(p, "rb") as fh:
            blobs.append(fh.read())
    with faults.active(FaultPlan.parse("io.decode:error:exc=decode,at=2")):
        out, ok = decodeResizeBatch(blobs, 16, 16)
    assert list(ok) == [True, False, True]  # stream survived the fault
    assert not out[1].any() and out[0].any() and out[2].any()
    out2, ok2 = decodeResizeBatch(blobs, 16, 16)  # plan gone: all decode
    assert list(ok2) == [True, True, True]


def test_bench_lines_stamp_faults_spec(monkeypatch):
    import bench

    faults.clear()  # the stage may run with SPARKDL_FAULTS exported
    lines = []
    monkeypatch.setattr(bench, "_print_line",
                        lambda s: lines.append(json.loads(s)))
    monkeypatch.setattr(bench, "_LINES", {})
    bench.emit("x", "m", 1.0, "u")
    assert lines[-1]["faults"] == "none"
    plan = FaultPlan.parse("seed=2;engine.dispatch:error:at=1")
    with faults.active(plan):
        bench.emit("x", "m", 1.0, "u")
    assert lines[-1]["faults"] == plan.spec  # chaos runs self-describe


# -- the acceptance chaos e2e ----------------------------------------------

def test_chaos_e2e_serving_plus_map_batches(model):
    """ISSUE 4 acceptance: one seeded plan injects one transient
    dispatch error, one pipeline gather-thread crash, and one queue-full
    storm into a CPU-backend serving + map_batches run.  All non-shed
    requests get correct outputs, health() transitions degraded->ready,
    and nothing is left wedged."""
    variables, x = model
    plan = FaultPlan.parse(
        "seed=7;"
        "engine.dispatch:error:exc=transient,at=4,times=1;"
        "pipeline.gather:error:exc=transient,at=2,times=1;"
        "serving.admit:error:exc=queue_full,at=9,times=1,retry_after=0.02")

    ref_eng = InferenceEngine(_fn, variables, device_batch_size=8)
    ref_rows = np.concatenate(
        [np.asarray(o) for o in ref_eng.map_batches([x], pipeline=False)])

    shed = []
    results = {}
    with faults.active(plan):
        # -- serving phase: sequential predicts make the dispatch order
        # (and thus the seeded plan's firing points) deterministic
        with Server(_fn, variables, max_batch_size=8, max_wait_ms=2,
                    bucket_sizes=[8], dispatch_retries=2,
                    breaker_threshold=8) as srv:
            srv.warmup(x[0])  # engine.dispatch call #1
            for i in range(16):
                try:
                    results[i] = np.asarray(srv.predict(x[i]))
                except QueueFullError as e:  # the injected storm
                    assert e.retry_after_s > 0
                    shed.append(i)
            h = srv.health()
        # exactly one storm reject; every other request served correctly
        assert shed == [8]
        for i, row in results.items():
            np.testing.assert_array_equal(row, ref_rows[i])
        # the transient dispatch error degraded health; the engine-level
        # retry absorbed it and the next served batch restored ready
        states = [t["state"] for t in h["transitions"]]
        assert "degraded" in states
        assert states[-1] == "ready" or h["state"] == "closed"
        assert states[states.index("degraded"):].count("ready") >= 1
        assert h["last_error"]["type"] == "InjectedTransientError"

        # -- map_batches phase, same plan: the gather-thread crash
        eng = InferenceEngine(_fn, variables, device_batch_size=8,
                              dispatch_retries=2)
        batches = [x[i:i + 8] for i in range(0, len(x), 8)]
        with pytest.raises(PipelineStageError) as ei:
            list(eng.map_batches(list(batches), pipeline=True))
        assert ei.value.stage == "gather"
        _no_stack_threads()  # crashed run drained cleanly
        out = [np.asarray(o) for o in eng.map_batches(list(batches),
                                                      pipeline=True)]
        np.testing.assert_array_equal(np.concatenate(out), ref_rows)

    # every planned fault actually fired exactly once
    stats = plan.stats()
    assert stats["engine.dispatch"]["fired"] == 1
    assert stats["pipeline.gather"]["fired"] == 1
    assert stats["serving.admit"]["fired"] == 1
    # join-with-timeout asserts: no thread or queue left wedged
    _no_stack_threads()


# -- kill the driver -------------------------------------------------------

def test_bench_artifact_survives_sigkill(tmp_path):
    """ISSUE 4 acceptance: SIGKILL bench.py mid-run; the incremental
    fsync'd JSONL artifact still holds a valid line for every completed
    config — a driver that lost its stdout capture to the kill still has
    every config that finished.  Two chip-free configs, so the run needs
    no accelerator: the kill lands once the first one's line is on disk,
    while the second (a ~15 s virtual day) is still under way."""
    artifact = tmp_path / "bench_lines.jsonl"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "SPARKDL_BENCH_CONFIGS": "pipeline,cache",
        "SPARKDL_BENCH_ARTIFACT": str(artifact),
        "SPARKDL_BENCH_TRACE": "0",
        "SPARKDL_FAULTS": "seed=5;io.decode:error:at=1000000",
    })
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["SPARKDL_COMPILE_CACHE"] = str(tmp_path / "cc")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL, start_new_session=True)
    try:
        # wait for the first COMPLETED config line, then kill mid-run
        deadline = time.monotonic() + 240
        seen_pipeline = False
        while time.monotonic() < deadline and not seen_pipeline:
            if proc.poll() is not None:
                break  # finished early: artifact must still be complete
            if artifact.exists():
                seen_pipeline = any(
                    '"config": "pipeline"' in ln
                    for ln in artifact.read_text().splitlines())
            time.sleep(0.25)
        assert artifact.exists(), "no artifact written before kill"
        if proc.poll() is None:
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    lines = artifact.read_text().splitlines()
    assert lines, "artifact empty — the crash-safe contract failed"
    recs = [json.loads(ln) for ln in lines]  # every line is valid JSON
    # the completed config's full record survived the SIGKILL, stamped
    # with the chaos plan it ran under and the device that measured it
    pipeline = [r for r in recs if r.get("config") == "pipeline"]
    assert pipeline and "value" in pipeline[0]
    assert pipeline[0]["faults"].endswith("io.decode:error:at=1000000")
    assert pipeline[0]["device"]["platform"] == "cpu"
