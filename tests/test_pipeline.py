"""Pipelined host/device execution tests (parallel.pipeline).

Contracts pinned here:
  * bit-identical outputs to the serial path on every scoring surface —
    engine (``map_batches``/``__call__``), transformer, UDF, and serving;
  * the synthetic slow-device benchmark proves the overlap: >= 1.5x
    throughput vs ``SPARKDL_PIPELINE=0`` with a simulated 100 ms dispatch
    latency on the CPU backend (the tier-1 contract run-tests.sh guards);
  * pipelined ``__call__`` streams into ONE preallocated output — a frame
    much larger than the in-flight window keeps peak host chunk residency
    bounded (no per-chunk accumulation list);
  * the ``SPARKDL_PIPELINE=0`` escape hatch, error propagation, and
    worker-thread cleanup on early consumer abandonment.
"""

import threading
import time
import weakref

import numpy as np
import pytest

from sparkdl_tpu.parallel import engine as engine_mod
from sparkdl_tpu.parallel.engine import InferenceEngine
from sparkdl_tpu.parallel.pipeline import (PipelinedRunner,
                                           pipeline_enabled_from_env,
                                           pipeline_stage_summary,
                                           synthetic_overlap_benchmark)
from sparkdl_tpu.utils.metrics import Metrics


def _fn(variables, x):
    import jax.numpy as jnp

    return jnp.tanh(x @ variables["w"] + variables["b"])


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(7)
    variables = {
        "w": rng.normal(size=(12, 5)).astype(np.float32),
        "b": rng.normal(size=(5,)).astype(np.float32),
    }
    x = rng.normal(size=(145, 12)).astype(np.float32)
    return variables, x


def _pipeline_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("sparkdl-pipeline")]


def _wait_threads_gone(timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not _pipeline_threads():
            return True
        time.sleep(0.02)
    return False


# -- env knob --------------------------------------------------------------

def test_pipeline_env_knob(monkeypatch):
    monkeypatch.delenv("SPARKDL_PIPELINE", raising=False)
    assert pipeline_enabled_from_env()
    for off in ("0", "false", "OFF", "no"):
        monkeypatch.setenv("SPARKDL_PIPELINE", off)
        assert not pipeline_enabled_from_env()
    monkeypatch.setenv("SPARKDL_PIPELINE", "1")
    assert pipeline_enabled_from_env()


def test_escape_hatch_never_builds_a_runner(setup, monkeypatch):
    """SPARKDL_PIPELINE=0 must route through the serial path without even
    constructing a PipelinedRunner."""
    variables, x = setup
    monkeypatch.setenv("SPARKDL_PIPELINE", "0")

    def boom(*a, **k):
        raise AssertionError("PipelinedRunner built despite the escape "
                             "hatch")

    monkeypatch.setattr(engine_mod, "PipelinedRunner", boom)
    eng = InferenceEngine(_fn, variables, device_batch_size=16)
    ref = np.tanh(x @ variables["w"] + variables["b"])
    np.testing.assert_allclose(eng(x), ref, rtol=1e-5, atol=1e-6)
    got = np.concatenate(list(eng.map_batches([x])), axis=0)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


# -- engine parity ---------------------------------------------------------

@pytest.mark.parametrize("bpd", [1, 3])
def test_map_batches_bit_identical_to_serial(setup, bpd):
    """Same programs, same pad/trim, same order — the pipelined stream is
    byte-for-byte the serial stream, ragged chunks and ragged tail groups
    included."""
    variables, x = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=16,
                          batches_per_dispatch=bpd)
    chunks = [x[:60], x[60:63], x[63:]]
    serial = list(eng.map_batches(iter(chunks), pipeline=False))
    piped = list(eng.map_batches(iter(chunks), pipeline=True))
    assert len(serial) == len(piped)
    for a, b in zip(serial, piped):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert _wait_threads_gone()


def test_call_bit_identical_to_serial_pytree(setup):
    """Pipelined __call__ on pytree outputs with integer leaves: the
    preallocated-stream result equals the serial concatenation exactly,
    and integer leaves are never floated."""
    import jax.numpy as jnp

    variables, x = setup

    def fn(v, xb):
        y = jnp.tanh(xb @ v["w"] + v["b"])
        return {"y": y, "ids": jnp.argmax(y, axis=-1)}

    eng = InferenceEngine(fn, variables, device_batch_size=8,
                          output_host_dtype=np.float32)
    a = eng(x, pipeline=False)
    b = eng(x, pipeline=True)
    np.testing.assert_array_equal(a["y"], b["y"])
    np.testing.assert_array_equal(a["ids"], b["ids"])
    assert b["ids"].dtype.kind in "iu"
    assert b["y"].dtype == np.float32


def test_single_piece_call_skips_worker_threads(setup, monkeypatch):
    """Inputs that fit one device batch (the serving micro-batch shape)
    have nothing to overlap: the call must not pay the thread hop."""
    variables, x = setup

    def boom(*a, **k):
        raise AssertionError("runner built for a single-piece call")

    monkeypatch.setattr(engine_mod, "PipelinedRunner", boom)
    eng = InferenceEngine(_fn, variables, device_batch_size=16)
    out = eng(x[:10], pipeline=True)
    ref = np.tanh(x[:10] @ variables["w"] + variables["b"])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_pipelined_grouped_tail_uses_plain_program(setup, monkeypatch):
    """The grouped-dispatch ragged tail must run through the plain
    per-batch program in the pipelined stages too — never padded with
    whole zero batches."""
    variables, x = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=16,
                          batches_per_dispatch=3)
    calls = {"group": 0, "plain": 0}
    lock = threading.Lock()
    orig_group, orig_plain = eng._dispatch_group, eng.run_padded

    def spy_group(stacked):
        with lock:
            calls["group"] += 1
        return orig_group(stacked)

    def spy_plain(batch):
        with lock:
            calls["plain"] += 1
        return orig_plain(batch)

    monkeypatch.setattr(eng, "_dispatch_group", spy_group)
    monkeypatch.setattr(eng, "run_padded", spy_plain)
    out = eng(np.concatenate([x[:45], x[:19]]), pipeline=True)  # 4 pieces
    assert out.shape[0] == 64
    assert calls == {"group": 1, "plain": 1}  # one full group, 1-piece tail


# -- host-memory contract --------------------------------------------------

def test_large_frame_call_preallocates_and_bounds_residency(setup,
                                                            monkeypatch):
    """A frame MUCH larger than the in-flight window (48 chunks vs
    window 2) through pipelined __call__: the output is preallocated once
    and chunks are released as they are copied in — at no point does a
    per-chunk accumulation list hold the stream."""
    variables, _ = setup
    rng = np.random.default_rng(11)
    n_chunks = 48
    x = rng.normal(size=(8 * n_chunks, 12)).astype(np.float32)
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    ref = eng(x, pipeline=False)

    refs, peaks = [], []
    orig_trim = eng._trim

    def spy_trim(out, nn):
        res = orig_trim(out, nn)
        refs.append(weakref.ref(res))
        peaks.append(sum(1 for r in refs if r() is not None))
        return res

    monkeypatch.setattr(eng, "_trim", spy_trim)
    before = eng.metrics.counters.get("engine_call_prealloc", 0)
    out = eng(x, pipeline=True)
    np.testing.assert_array_equal(out, ref)
    assert eng.metrics.counters["engine_call_prealloc"] == before + 1
    assert len(refs) == n_chunks
    # gathered chunks die as soon as they are copied into the preallocated
    # output: simultaneous live chunks stay O(queue depths), never O(n)
    assert max(peaks) <= 8, max(peaks)


# -- failure / cleanup -----------------------------------------------------

def test_producer_error_propagates_to_consumer(setup):
    variables, x = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=16)

    def bad():
        yield x[:16]
        raise RuntimeError("decode exploded")

    with pytest.raises(RuntimeError, match="decode exploded"):
        list(eng.map_batches(bad(), pipeline=True))
    assert _wait_threads_gone()


def test_consumer_abandonment_stops_worker_threads(setup):
    """Closing the output iterator early (a raising downstream consumer)
    must cancel all three stages — no producer left blocked on a full
    queue, no leaked thread."""
    variables, x = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    it = eng.map_batches([x], pipeline=True)
    first = next(it)
    assert first.shape[0] == 8
    it.close()
    assert _wait_threads_gone()


# -- metrics + the overlap contract ----------------------------------------

def test_stage_metrics_recorded(setup):
    variables, x = setup
    m = Metrics()
    eng = InferenceEngine(_fn, variables, device_batch_size=8, metrics=m)
    list(eng.map_batches([x], pipeline=True))
    assert m.counters.get("pipeline.dispatches") == 19  # ceil(145/8)
    assert m.counters.get("pipeline.gathers") == 19
    assert "pipeline.prep_q_depth" in m.histograms
    assert "pipeline.inflight_q_depth" in m.histograms
    assert "pipeline.out_q_depth" in m.histograms
    summary = pipeline_stage_summary(m)
    assert summary["pipeline.dispatches"] == 19
    assert any(k.endswith("_depth.mean") for k in summary)


def test_synthetic_overlap_benchmark_speedup():
    """THE tier-1 overlap contract: with a simulated 100 ms blocking
    dispatch (a slow-device regime) and 100 ms host prepare per batch,
    the pipelined path must be >= 1.5x the serial path on the CPU backend
    (ideal is 2x; the bound leaves headroom for thread scheduling noise).
    Deterministic: sleep-dominated, parity-checked inside."""
    result = synthetic_overlap_benchmark()  # 6 batches, 100 ms / 100 ms
    assert result["speedup"] >= 1.5, result
    assert result["stages"]["pipeline.dispatches"] == result["n_batches"]
    # the stall ledger tells the bottleneck story: with prep == dispatch
    # cost, gather mostly waits on the device — its in-stall dominates
    assert "pipeline.gather_in_stall_s" in result["stages"]


# -- surface parity (transformer / UDF / serving) --------------------------

def _image_frame(n=7, h=16, w=12, null_at=2):
    import pyarrow as pa

    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.image.schema import imageArrayToStruct, imageSchema

    rng = np.random.default_rng(5)
    structs = [imageArrayToStruct(
        (rng.random((h, w, 3)) * 255).astype(np.uint8), origin=f"r{i}")
        for i in range(n)]
    if null_at is not None:
        structs[null_at] = None
    return DataFrame(pa.table(
        {"image": pa.array(structs, type=imageSchema)}))


def test_transformer_surface_parity(monkeypatch):
    """TFImageTransformer.transform and transformStream emit bit-identical
    columns with the pipeline on and off."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers.named_image import TFImageTransformer

    df = _image_frame()
    mf = ModelFunction(
        fn=lambda v, x: (x.astype("float32").reshape(x.shape[0], -1)
                         @ v["w"]),
        variables={"w": np.linspace(-1, 1, 16 * 12 * 3 * 4).reshape(
            16 * 12 * 3, 4).astype(np.float32)})

    def run():
        t = TFImageTransformer(inputCol="image", outputCol="out",
                               modelFunction=mf, inputSize=[16, 12],
                               batchSize=2)
        full = t.transform(df).table.column("out").to_pylist()
        streamed = []
        for rb in t.transformStream(df.table.to_batches(max_chunksize=3)):
            streamed.extend(rb.column(rb.schema.names.index("out"))
                            .to_pylist())
        return full, streamed

    monkeypatch.setenv("SPARKDL_PIPELINE", "0")
    full_serial, stream_serial = run()
    monkeypatch.setenv("SPARKDL_PIPELINE", "1")
    full_piped, stream_piped = run()
    assert full_piped == full_serial          # bit-exact floats
    assert stream_piped == stream_serial
    assert full_serial[2] is None             # null row contract intact


def test_udf_surface_parity(monkeypatch):
    """register_image_udf scoring emits bit-identical columns with the
    pipeline on and off."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.udf import UDFRegistry, register_image_udf

    df = _image_frame()
    mf = ModelFunction(
        fn=lambda v, x: x.reshape(x.shape[0], -1) @ v["w"],
        variables={"w": np.linspace(0, 1, 16 * 12 * 3 * 2).reshape(
            16 * 12 * 3, 2).astype(np.float32)})

    def run():
        reg = UDFRegistry()
        register_image_udf("p", mf, input_size=(16, 12), batch_size=2,
                           registry=reg)
        out = reg.apply("p", df, "image", "scores")
        return out.table.column("scores").to_pylist()

    monkeypatch.setenv("SPARKDL_PIPELINE", "0")
    serial = run()
    monkeypatch.setenv("SPARKDL_PIPELINE", "1")
    piped = run()
    assert piped == serial
    assert serial[2] is None


def test_serving_surface_parity(monkeypatch):
    """Served rows are bit-identical with the pipeline on and off (the
    serving micro-batch is a single device batch, so it rides the
    single-piece fast path either way — this pins that equivalence)."""
    from sparkdl_tpu.serving import Server

    rng = np.random.default_rng(3)
    w = rng.normal(size=(12, 4)).astype(np.float32)

    def fn(v, x):
        import jax.numpy as jnp

        return jnp.tanh(x @ v["w"])

    xs = rng.normal(size=(20, 12)).astype(np.float32)

    def run():
        with Server(fn, {"w": w}, max_batch_size=8, max_wait_ms=2.0) as srv:
            futs = [srv.submit(row) for row in xs]
            return [np.asarray(f.result()) for f in futs]

    monkeypatch.setenv("SPARKDL_PIPELINE", "0")
    serial = run()
    monkeypatch.setenv("SPARKDL_PIPELINE", "1")
    piped = run()
    for a, b in zip(serial, piped):
        np.testing.assert_array_equal(a, b)
