"""Inference-engine tests on the 8-device virtual CPU mesh.

The reference simulates multi-executor behavior with multiple local
partitions (SURVEY.md §4); the TPU analog is a virtual 8-device CPU mesh
(see conftest).  These tests assert the engine's fixed-shape padding, the
sharded execution path, and the streaming window produce exactly the same
numbers as a plain unsharded call.
"""

import numpy as np
import pytest

from sparkdl_tpu.parallel import InferenceEngine, get_mesh
from sparkdl_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def _fn(variables, x):
    # toy "model": affine + nonlinearity, batch on axis 0
    import jax.numpy as jnp

    return jnp.tanh(x @ variables["w"] + variables["b"])


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(3)
    variables = {
        "w": rng.normal(size=(12, 5)).astype(np.float32),
        "b": rng.normal(size=(5,)).astype(np.float32),
    }
    x = rng.normal(size=(45, 12)).astype(np.float32)
    ref = np.tanh(x @ variables["w"] + variables["b"])
    return variables, x, ref


def test_mesh_spans_all_devices():
    import jax

    mesh = get_mesh()
    assert mesh.size == len(jax.devices()) == 8
    assert mesh.shape[DATA_AXIS] == 8 and mesh.shape[MODEL_AXIS] == 1


def test_mesh_subset_and_validation():
    mesh = get_mesh(num_devices=4)
    assert mesh.size == 4
    with pytest.raises(ValueError, match="only"):
        get_mesh(num_devices=99)
    with pytest.raises(ValueError, match="does not divide"):
        get_mesh(num_devices=4, model_parallel=3)


def test_engine_matches_unsharded(setup):
    variables, x, ref = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=16)
    out = eng(x)
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_engine_rounds_batch_to_data_axis(setup):
    variables, x, ref = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=10)
    # 8-way data axis: 10 -> 16
    assert eng.device_batch_size == 16
    np.testing.assert_allclose(eng(x), ref, rtol=1e-5, atol=1e-6)


def test_engine_ragged_tail_is_trimmed(setup):
    variables, x, ref = setup
    # 45 rows, batch 32 -> chunks of 32 and 13 (padded to 32, trimmed)
    eng = InferenceEngine(_fn, variables, device_batch_size=32)
    out = eng(x)
    assert out.shape[0] == 45
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_engine_streaming_window(setup):
    variables, x, ref = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    batches = [x[:20], x[20:23], x[23:]]
    outs = list(eng.map_batches(batches, window=2))
    got = np.concatenate(outs, axis=0)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def _pieces_case(name):
    """chunks, and the real rows of the pieces they must be cut into."""
    rng = np.random.default_rng(4)
    x = rng.normal(size=(23, 12)).astype(np.float32)
    if name == "array":         # 2.5 device batches
        return [x[:20]], [8, 8, 4]
    if name == "pytree":
        ids = np.arange(20, dtype=np.int32)
        return [{"x": x[:20], "ids": ids}], [8, 8, 4]
    # a piece never spans two chunks: each chunk's tail is padded alone
    return [x[:20], x[20:]], [8, 8, 4, 3]


@pytest.mark.parametrize("name", ["array", "pytree", "two chunks"])
def test_iter_pieces_yields_rows_and_padded_pieces(setup, name):
    """``_iter_pieces``, the one host-prepare sequence both paths consume:
    ``(n_rows, padded_piece)`` in dispatch order, every piece of the
    compiled leading size, the real rows first and zeros behind them."""
    import jax

    variables, _, _ = setup
    eng = InferenceEngine(lambda v, b: b, variables, device_batch_size=8)
    chunks, want_rows = _pieces_case(name)
    pieces = list(eng._iter_pieces(iter(chunks)))
    assert [n for n, _ in pieces] == want_rows      # pairs, nothing else
    for n, padded in pieces:
        for leaf in jax.tree_util.tree_leaves(padded):
            assert leaf.shape[0] == 8
            assert not leaf[n:].any()

    def rows(trees):
        return jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda *parts: np.concatenate(parts), *trees))

    real = [jax.tree_util.tree_map(lambda a, n=n: a[:n], padded)
            for n, padded in pieces]
    for got, want in zip(rows(real), rows(chunks)):
        np.testing.assert_array_equal(got, want)


def test_engine_multicontroller_mesh_policy(setup, monkeypatch):
    """Scoring is per-controller: under multi-controller jax the DEFAULT
    mesh covers local devices only (the zoo transformers pass no mesh,
    so they keep working on pods), while an EXPLICIT mesh spanning other
    processes is refused loudly at construction (device_put of
    process-local numpy onto a global sharding fails confusingly at
    runtime otherwise)."""
    import jax

    from sparkdl_tpu.parallel import mesh as mesh_lib

    variables, x, ref = setup
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    # default mesh: local devices, scoring still works end to end
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    assert all(d.process_index == jax.process_index()
               for d in eng.mesh.devices.flat)
    np.testing.assert_allclose(eng(x), ref, rtol=1e-5, atol=1e-6)
    # explicit cross-process mesh: refused
    remote = mesh_lib.get_mesh()
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    with pytest.raises(NotImplementedError, match="single-controller"):
        InferenceEngine(_fn, variables, device_batch_size=8, mesh=remote)


def test_engine_empty_input_rejected(setup):
    variables, x, _ = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    with pytest.raises(ValueError, match="Empty"):
        eng(x[:0])


def test_engine_compute_dtype_bf16(setup):
    import jax.numpy as jnp

    variables, x, ref = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=16,
                          compute_dtype=jnp.bfloat16)
    out = np.asarray(eng(x), dtype=np.float32)
    # bf16 has ~3 decimal digits; loose tolerance
    np.testing.assert_allclose(out, ref, rtol=0.05, atol=0.05)


def test_engine_pytree_output(setup):
    variables, x, ref = setup

    def fn2(v, x):
        import jax.numpy as jnp

        y = jnp.tanh(x @ v["w"] + v["b"])
        return {"y": y, "norm": jnp.sum(y * y, axis=-1)}

    eng = InferenceEngine(fn2, variables, device_batch_size=16)
    out = eng(x)
    np.testing.assert_allclose(out["y"], ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(out["norm"], (ref * ref).sum(-1),
                               rtol=1e-4, atol=1e-5)


def test_engine_output_is_actually_sharded(setup):
    """The compiled call must shard the batch over the data axis (this is
    the chips-get-rows contract, not just a numerical one)."""
    import jax

    variables, x, _ = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=16)
    dev_out = eng.run_padded(np.zeros((16, 12), np.float32))
    shards = dev_out.addressable_shards
    assert len(shards) == 8
    assert all(s.data.shape == (2, 5) for s in shards)


def test_engine_call_bounds_inflight_window(setup, monkeypatch):
    """__call__ must gather chunk k-window before dispatching chunk k+1 —
    device residency stays O(window), not O(n_chunks) (ADVICE round 1)."""
    variables, x, ref = setup
    eng = InferenceEngine(_fn, variables, device_batch_size=8)
    events = []
    orig_run, orig_trim = eng.run_padded, eng._trim

    def spy_run(batch):
        events.append("dispatch")
        return orig_run(batch)

    monkeypatch.setattr(eng, "run_padded", spy_run)
    monkeypatch.setattr(eng, "_trim",
                        lambda out, n: (events.append("gather"),
                                        orig_trim(out, n))[1])
    # serial path pinned: single-thread event ordering is the invariant
    # under test (pipelined residency bounds live in test_pipeline)
    out = eng(x, window=2, pipeline=False)  # 45 rows / 8 = 6 chunks
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    # With 6 chunks and window=2, the first gather must happen before the
    # last dispatch (not all dispatches first, as in round 1).
    first_gather = events.index("gather")
    last_dispatch = len(events) - 1 - events[::-1].index("dispatch")
    assert first_gather < last_dispatch, events
    # and never more than window+1 dispatches outstanding
    outstanding = peak = 0
    for e in events:
        outstanding += 1 if e == "dispatch" else -1
        peak = max(peak, outstanding)
    assert peak <= 3, events


def test_output_host_dtype_casts_after_fetch():
    """output_host_dtype fetches the compute dtype and casts on the host:
    results are bit-identical to a device-side upcast (bf16->f32 widening
    is exact) while the gathered buffer is the narrow dtype."""
    import jax.numpy as jnp

    from sparkdl_tpu.parallel.engine import InferenceEngine, clear_engine_jit_cache

    clear_engine_jit_cache()
    w = np.linspace(-1, 1, 12).reshape(3, 4).astype(np.float32)

    def fn_raw(v, x):  # bf16 out
        return (jnp.asarray(x, jnp.bfloat16) @ v["w"].astype(jnp.bfloat16))

    def fn_up(v, x):   # device-side upcast of the same computation
        return fn_raw(v, x).astype(jnp.float32)

    x = np.random.default_rng(0).normal(size=(10, 3)).astype(np.float32)
    host_cast = InferenceEngine(fn_raw, {"w": w}, device_batch_size=8,
                                output_host_dtype=np.float32)(x)
    assert host_cast.dtype == np.float32
    # without the option, outputs come back in the compute dtype; the host
    # cast must be exactly the f32 widening of those bf16 values
    raw = InferenceEngine(fn_raw, {"w": w}, device_batch_size=8)(x)
    assert raw.dtype != np.float32
    np.testing.assert_array_equal(host_cast, raw.astype(np.float32))
    # and within bf16 tolerance of the device-side-upcast program (XLA may
    # fuse the upcast and skip the intermediate bf16 rounding, so exact
    # equality with THAT program is not guaranteed)
    dev_cast = InferenceEngine(fn_up, {"w": w}, device_batch_size=8)(x)
    np.testing.assert_allclose(host_cast, dev_cast, rtol=2e-2, atol=2e-2)


def test_output_host_dtype_preserves_integer_leaves():
    """Integer outputs (e.g. argmax class ids) must pass through the
    host cast untouched."""
    import jax.numpy as jnp

    from sparkdl_tpu.parallel.engine import InferenceEngine

    def fn(v, x):
        logits = jnp.asarray(x, jnp.bfloat16) @ v["w"].astype(jnp.bfloat16)
        return {"scores": logits, "ids": jnp.argmax(logits, axis=-1)}

    w = np.eye(3, dtype=np.float32)
    x = np.random.default_rng(1).normal(size=(5, 3)).astype(np.float32)
    out = InferenceEngine(fn, {"w": w}, device_batch_size=8,
                          output_host_dtype=np.float32)(x)
    assert out["scores"].dtype == np.float32
    assert np.issubdtype(out["ids"].dtype, np.integer)


def test_placed_variables_are_taken_not_copied():
    """A leaf that is already on the device as the engine wants it is the
    engine's leaf: the same buffer, no second copy of the weights.  Host
    leaves and leaves laid out otherwise are placed as before."""
    import jax
    import jax.numpy as jnp

    eng0 = InferenceEngine(lambda v, x: x @ v["w"],
                           {"w": np.eye(4, dtype=np.float32)},
                           device_batch_size=8)
    placed = eng0.variables["w"]            # as an engine lays it out
    elsewhere = jax.device_put(jnp.ones((4,), jnp.float32), jax.devices()[0])
    eng = InferenceEngine(
        lambda v, x: x @ v["w"] + v["b"] + v["c"],
        {"w": placed, "b": elsewhere, "c": np.zeros((4,), np.float32)},
        device_batch_size=8)
    assert eng.variables["w"] is placed
    assert ([s.data.unsafe_buffer_pointer()
             for s in eng.variables["w"].addressable_shards]
            == [s.data.unsafe_buffer_pointer()
                for s in placed.addressable_shards])
    for name in ("b", "c"):
        assert eng.variables[name].sharding.is_equivalent_to(
            placed.sharding, 1)
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    np.testing.assert_allclose(eng(x), x + 1.0)
