"""Pipelined host/device execution tests (parallel.pipeline).

Contracts pinned here:
  * bit-identical outputs to the calling-thread path (``pipeline=False``)
    on every scoring surface — engine (``map_batches``/``__call__``),
    transformer, UDF, and serving;
  * the synthetic slow-device benchmark proves the overlap: >= 1.5x
    throughput vs ``pipeline=False`` with a simulated 100 ms dispatch
    latency on the CPU backend (the tier-1 contract run-tests.sh guards);
  * pipelined ``__call__`` streams into ONE preallocated output — a frame
    much larger than the in-flight window keeps peak host chunk residency
    bounded (no per-chunk accumulation list);
  * the ``pipeline=False`` argument, error propagation, and
    worker-thread cleanup on early consumer abandonment.
"""

import threading
import time
import weakref

import numpy as np
import pytest

from sparkdl_tpu.parallel import engine as engine_mod
from sparkdl_tpu.parallel.engine import InferenceEngine
from sparkdl_tpu.parallel.pipeline import (pipeline_stage_summary,
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


# -- the calling-thread argument -------------------------------------------

def test_escape_hatch_never_builds_a_runner(setup, monkeypatch):
    """``pipeline=False`` must route through the calling-thread path
    without even constructing a PipelinedRunner."""
    variables, x = setup

    def boom(*a, **k):
        raise AssertionError("PipelinedRunner built despite "
                             "pipeline=False")

    monkeypatch.setattr(engine_mod, "PipelinedRunner", boom)
    eng = InferenceEngine(_fn, variables, device_batch_size=16)
    ref = np.tanh(x @ variables["w"] + variables["b"])
    np.testing.assert_allclose(eng(x, pipeline=False), ref, rtol=1e-5,
                               atol=1e-6)
    got = np.concatenate(list(eng.map_batches([x], pipeline=False)), axis=0)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


# -- engine parity ---------------------------------------------------------

@pytest.mark.parametrize("window", [1, 3])
def test_map_batches_bit_identical_to_serial(setup, window):
    """Same program, same pad/trim, same order — the pipelined stream is
    byte-for-byte the calling-thread stream, ragged chunks included,
    whatever the in-flight window."""
    variables, x = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=16)
    chunks = [x[:60], x[60:63], x[63:]]
    serial = list(eng.map_batches(iter(chunks), window=window,
                                  pipeline=False))
    piped = list(eng.map_batches(iter(chunks), window=window,
                                 pipeline=True))
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


def test_consumer_garbage_collection_stops_worker_threads(setup):
    """A consumer that simply DROPS the iterator (a function return, an
    exception unwound past it) never calls ``close()``: CPython finalizes
    the generator when it is collected, its ``finally`` sets the stop
    flag, and all three stages leave their bounded queue loops."""
    import gc

    variables, x = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    it = eng.map_batches([x], pipeline=True)
    assert next(it).shape[0] == 8
    assert len(_pipeline_threads()) == 3
    del it
    gc.collect()
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


@pytest.fixture()
def engine_calls(monkeypatch):
    """Every ``InferenceEngine.__call__`` a surface makes, as ``(engine,
    batch)``, so a test can put the SAME batch through the SAME engine on
    the calling thread."""
    calls = []
    real = InferenceEngine.__call__

    def spy(self, batch, *args, **kwargs):
        calls.append((self, batch))
        return real(self, batch, *args, **kwargs)

    monkeypatch.setattr(InferenceEngine, "__call__", spy)
    return calls


def test_transformer_surface_parity():
    """TFImageTransformer.transform and transformStream (the engine's
    pipelined ``map_batches``) emit the rows the same engine gives for the
    same batch on the calling thread, bit for bit."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.image.io import arrowStructsToBatch
    from sparkdl_tpu.parallel.engine import get_cached_engine
    from sparkdl_tpu.transformers.named_image import TFImageTransformer

    df = _image_frame()
    mf = ModelFunction(
        fn=lambda v, x: (x.astype("float32").reshape(x.shape[0], -1)
                         @ v["w"]),
        variables={"w": np.linspace(-1, 1, 16 * 12 * 3 * 4).reshape(
            16 * 12 * 3, 4).astype(np.float32)})

    t = TFImageTransformer(inputCol="image", outputCol="out",
                           modelFunction=mf, inputSize=[16, 12],
                           batchSize=2)
    full = t.transform(df).table.column("out").to_pylist()
    streamed = []
    for rb in t.transformStream(df.table.to_batches(max_chunksize=3)):
        streamed.extend(rb.column(rb.schema.names.index("out"))
                        .to_pylist())

    eng = get_cached_engine(t, mf, device_batch_size=2)  # the stage's own
    batch, ok = arrowStructsToBatch(df.table.column("image"), 16, 12,
                                    compact=True)
    want = iter(np.asarray(eng(batch, pipeline=False), np.float32).tolist())
    serial = [next(want) if good else None for good in ok]
    assert full == serial                     # bit-exact floats
    assert streamed == serial
    assert serial[2] is None                  # null row contract intact


def test_udf_surface_parity(engine_calls):
    """register_image_udf scoring (the engine's pipelined ``__call__`` over
    three device batches) emits the rows the same engine gives for the
    same batch on the calling thread, bit for bit."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.udf import UDFRegistry, register_image_udf

    df = _image_frame(n=20)
    mf = ModelFunction(
        fn=lambda v, x: x.reshape(x.shape[0], -1) @ v["w"],
        variables={"w": np.linspace(0, 1, 16 * 12 * 3 * 2).reshape(
            16 * 12 * 3, 2).astype(np.float32)})

    reg = UDFRegistry()
    register_image_udf("p", mf, input_size=(16, 12), batch_size=2,
                       registry=reg)
    piped = reg.apply("p", df, "image", "scores").table.column(
        "scores").to_pylist()

    (eng, batch), = engine_calls
    assert batch.shape[0] == 19 > 2 * eng.device_batch_size
    want = iter(np.asarray(eng(batch, pipeline=False),
                           np.float32).tolist())
    serial = [None if i == 2 else next(want) for i in range(20)]
    assert piped == serial
    assert serial[2] is None


def test_serving_surface_parity(engine_calls):
    """Every served row is the row the same bucket engine gives for the
    same micro-batch with ``pipeline=False`` (the serving micro-batch is a
    single device batch, so it rides the single-piece path either way —
    this pins that equivalence)."""
    from sparkdl_tpu.serving import Server

    rng = np.random.default_rng(3)
    w = rng.normal(size=(12, 4)).astype(np.float32)

    def fn(v, x):
        import jax.numpy as jnp

        return jnp.tanh(x @ v["w"])

    xs = rng.normal(size=(20, 12)).astype(np.float32)

    with Server(fn, {"w": w}, max_batch_size=8, max_wait_ms=2.0) as srv:
        futs = [srv.submit(row) for row in xs]
        served = [np.asarray(f.result()) for f in futs]

    serial = {}
    for eng, batch in list(engine_calls):
        out = eng(batch, pipeline=False)
        serial.update((row.tobytes(), o) for row, o in zip(batch, out))
    for row, got in zip(xs, served):
        np.testing.assert_array_equal(got, serial[row.tobytes()])
