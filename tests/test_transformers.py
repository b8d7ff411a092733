"""Transformer-stage tests.

Mirrors the reference's transformer test strategy (SURVEY.md §4): DataFrame
path vs. in-process numpy path equality; null-row handling; Pipeline
chaining; partition-count variation.  Zoo stages are tested with a tiny fake
module injected into the model cache (plumbing) — full-architecture numeric
parity is covered by test_models.py.
"""

import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.frame import DataFrame
from sparkdl_tpu.graph.function import ModelFunction
from sparkdl_tpu.image.io import readImages
from sparkdl_tpu.models import get_model_spec
from sparkdl_tpu.transformers import (DeepImageFeaturizer, DeepImagePredictor,
                                      ModelTransformer, Pipeline,
                                      TFImageTransformer, TFTransformer,
                                      Transformer)
from sparkdl_tpu.transformers import named_image as ni


class _TinyZooModule:
    """Stands in for a flax zoo module: deterministic function of the input
    so plumbing (decode, resize, null alignment, batching) is checkable."""

    def __init__(self, feature_size=2048, classes=1000):
        self.feature_size = feature_size
        self.classes = classes

    def apply(self, variables, x, train=False, features=False):
        import jax.numpy as jnp

        m = jnp.mean(x, axis=(1, 2, 3), keepdims=False)  # [B]
        dim = self.feature_size if features else self.classes
        idx = jnp.arange(dim, dtype=jnp.float32)
        return m[:, None] * 0.01 + idx[None, :] * 1e-4


@pytest.fixture()
def fake_resnet(monkeypatch):
    spec = get_model_spec("ResNet50")
    module = _TinyZooModule(feature_size=spec.feature_size)
    monkeypatch.setitem(ni._MODEL_CACHE, "ResNet50", (module, {}))
    # engines cache per (name, featurize, batch) — clear so the fake is used
    ni._ENGINE_CACHE.clear()
    yield spec
    ni._ENGINE_CACHE.clear()


@pytest.fixture()
def image_df(fixture_images):
    # 3 decodable images + 1 null row (bad jpeg)
    return readImages(fixture_images["dir"])


def test_featurizer_plumbing(fake_resnet, image_df):
    ft = DeepImageFeaturizer(inputCol="image", outputCol="features",
                             modelName="resnet50", batchSize=8)
    out = ft.transform(image_df)
    rows = out.collect()
    assert len(rows) == 4
    nulls = [r for r in rows if r["features"] is None]
    vals = [r for r in rows if r["features"] is not None]
    assert len(nulls) == 1 and len(vals) == 3  # bad jpeg stays null
    assert all(len(r["features"]) == fake_resnet.feature_size for r in vals)
    # deterministic across runs
    out2 = ft.transform(image_df)
    v1 = [r["features"] for r in out.collect() if r["features"]]
    v2 = [r["features"] for r in out2.collect() if r["features"]]
    np.testing.assert_allclose(v1, v2)


def test_predictor_raw_and_decoded(fake_resnet, image_df):
    pred = DeepImagePredictor(inputCol="image", outputCol="probs",
                              modelName="ResNet50", batchSize=8)
    rows = pred.transform(image_df).collect()
    vals = [r for r in rows if r["probs"] is not None]
    assert all(len(r["probs"]) == 1000 for r in vals)

    topk = DeepImagePredictor(inputCol="image", outputCol="preds",
                              modelName="ResNet50", decodePredictions=True,
                              topK=3, batchSize=8)
    rows = topk.transform(image_df).collect()
    vals = [r for r in rows if r["preds"] is not None]
    assert len(vals) == 3
    for r in vals:
        assert len(r["preds"]) == 3
        probs = [p["probability"] for p in r["preds"]]
        assert probs == sorted(probs, reverse=True)
        assert all(isinstance(p["class"], str) for p in r["preds"])


def test_named_transformer_rejects_unknown_model():
    with pytest.raises(TypeError, match="not in the supported list"):
        DeepImageFeaturizer(inputCol="image", outputCol="f",
                            modelName="NoSuchNet")


def test_tf_image_transformer_vector_and_image(image_df):
    mf = ModelFunction(fn=lambda v, x: x.astype("float32") * v["scale"],
                       variables={"scale": np.float32(0.5)})
    t = TFImageTransformer(inputCol="image", outputCol="out",
                           modelFunction=mf, inputSize=[24, 20],
                           outputMode="vector", batchSize=8)
    rows = t.transform(image_df).collect()
    vals = [r for r in rows if r["out"] is not None]
    assert len(vals) == 3
    assert all(len(r["out"]) == 24 * 20 * 3 for r in vals)

    t_img = TFImageTransformer(inputCol="image", outputCol="img_out",
                               modelFunction=mf, inputSize=[24, 20],
                               outputMode="image", batchSize=8)
    rows = t_img.transform(image_df).collect()
    vals = [r for r in rows if r["img_out"] is not None]
    assert all(r["img_out"]["height"] == 24 and r["img_out"]["width"] == 20
               and r["img_out"]["mode"] == 21  # CV_32FC3
               for r in vals)


def test_model_transformer_matches_numpy(rng):
    import jax.numpy as jnp

    w = rng.normal(size=(6, 3)).astype(np.float32)
    x = rng.normal(size=(11, 6)).astype(np.float32)
    df = DataFrame({"feats": [list(map(float, r)) for r in x]})
    mf = ModelFunction(fn=lambda v, t: jnp.tanh(t @ v["w"]),
                       variables={"w": w})
    mt = ModelTransformer(inputCol="feats", outputCol="out",
                          modelFunction=mf, batchSize=4)
    got = np.asarray([r["out"] for r in mt.transform(df).collect()])
    np.testing.assert_allclose(got, np.tanh(x @ w), rtol=1e-5, atol=1e-6)


def test_tf_transformer_mapping(rng):
    xa = rng.normal(size=(9, 4)).astype(np.float32)
    xb = rng.normal(size=(9, 4)).astype(np.float32)
    df = DataFrame({"colA": [list(map(float, r)) for r in xa],
                    "colB": [list(map(float, r)) for r in xb]})
    mf = ModelFunction(
        fn=lambda v, d: {"sum": d["a"] + d["b"], "diff": d["a"] - d["b"]},
        variables={}, input_names=("a", "b"), output_names=("sum", "diff"))
    t = TFTransformer(modelFunction=mf,
                      inputMapping={"colA": "a", "colB": "b"},
                      outputMapping={"sum": "s", "diff": "d"},
                      batchSize=4)
    out = t.transform(df)
    s = np.asarray([r["s"] for r in out.collect()])
    d = np.asarray([r["d"] for r in out.collect()])
    np.testing.assert_allclose(s, xa + xb, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(d, xa - xb, rtol=1e-5, atol=1e-6)

    with pytest.raises(ValueError, match="unknown model inputs"):
        TFTransformer(modelFunction=mf, inputMapping={"colA": "nope"},
                      outputMapping={"sum": "s"}).transform(df)


@pytest.mark.parametrize("id_type", [pa.int32(), pa.uint16()])
def test_tf_transformer_counts_the_named_token_input(id_type):
    """Of several integer columns, the tokens the counters take are the
    values of the input the program names as its ids, whatever their
    integer type; the pixel column beside them is none."""
    import jax.numpy as jnp

    def fn(v, d):
        rows = d["ids"].shape[0]
        return {"y": d["pixels"].astype(jnp.float32).sum(axis=1),
                "expert_load": jnp.ones((rows, 3, 2), jnp.int32)}

    mf = ModelFunction(fn=fn, variables={}, input_names=("pixels", "ids"),
                       output_names=("y", "expert_load"),
                       counter_names=("expert_load",), token_input="ids")
    df = DataFrame(pa.table({
        "clip": pa.array([[1] * 12] * 5, type=pa.list_(pa.uint8())),
        "text": pa.array([[7, 8, 9]] * 5, type=pa.list_(id_type))}))
    stage = TFTransformer(modelFunction=mf,
                          inputMapping={"clip": "pixels", "text": "ids"},
                          outputMapping={"y": "y"}, batchSize=2)
    out = stage.transform(df)
    np.testing.assert_allclose(out.column_to_numpy("y").reshape(-1), [12.0] * 5)
    counters = stage.engine().metrics.snapshot_raw()["counters"]
    # 5 rows of 3 ids, over 3 expert layers
    assert counters["moe.tokens"] == 5 * 3 * 3
    assert counters["moe.pairs"] == 5 * 3 * 2


def test_pipeline_chains_stages(fake_resnet, image_df):
    class _Renamer(Transformer):
        def _transform(self, ds):
            return ds.withColumnRenamed("features", "fvec")

    pipe = Pipeline(stages=[
        DeepImageFeaturizer(inputCol="image", outputCol="features",
                            modelName="ResNet50", batchSize=8),
        _Renamer(),
    ])
    model = pipe.fit(image_df)
    out = model.transform(image_df)
    assert "fvec" in out.columns and "features" not in out.columns


def test_keras_transformer_end_to_end(tmp_path, rng):
    """modelFile contract: save a tiny Keras MLP, transform a frame of 1-D
    float arrays, parity vs. local keras predict (reference's
    keras_tensor_test pattern)."""
    import keras
    from keras import layers

    from sparkdl_tpu.transformers import KerasTransformer

    model = keras.Sequential([
        layers.Input((10,)),
        layers.Dense(6, activation="relu"),
        layers.Dense(3, activation="softmax"),
    ])
    path = str(tmp_path / "mlp.keras")
    model.save(path)
    x = rng.normal(size=(7, 10)).astype(np.float32)
    ref = model.predict(x, verbose=0)
    df = DataFrame({"in": [list(map(float, r)) for r in x]})
    kt = KerasTransformer(inputCol="in", outputCol="out", modelFile=path,
                          batchSize=4)
    got = np.asarray([r["out"] for r in kt.transform(df).collect()])
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_keras_image_file_transformer(tmp_path, fixture_images):
    import keras
    from keras import layers

    from sparkdl_tpu.transformers import KerasImageFileTransformer

    model = keras.Sequential([
        layers.Input((8, 8, 3)),
        layers.Conv2D(2, 3, padding="same", activation="relu"),
        layers.GlobalAveragePooling2D(),
    ])
    path = str(tmp_path / "cnn.keras")
    model.save(path)

    def loader(uri):
        from PIL import Image

        img = Image.open(uri).convert("RGB").resize((8, 8))
        return np.asarray(img, dtype=np.float32) / 255.0

    df = DataFrame({"uri": fixture_images["paths"]})
    t = KerasImageFileTransformer(inputCol="uri", outputCol="out",
                                  modelFile=path, imageLoader=loader,
                                  batchSize=4)
    rows = t.transform(df).collect()
    batch = np.stack([loader(u) for u in fixture_images["paths"]])
    ref = model.predict(batch, verbose=0)
    got = np.asarray([r["out"] for r in rows])
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_image_file_transformer(fixture_images):
    from sparkdl_tpu.transformers import ImageFileTransformer

    def loader(uri):
        from PIL import Image

        img = Image.open(uri).convert("RGB").resize((8, 8))
        return np.asarray(img, dtype=np.float32) / 255.0

    paths = fixture_images["paths"] + [fixture_images["bad"]]
    df = DataFrame({"uri": paths})
    mf = ModelFunction(fn=lambda v, x: x.reshape(x.shape[0], -1) @ v["w"],
                       variables={"w": np.ones((8 * 8 * 3, 2), np.float32)})
    t = ImageFileTransformer(inputCol="uri", outputCol="out",
                             modelFunction=mf, imageLoader=loader, batchSize=4)
    rows = t.transform(df).collect()
    assert len(rows) == 4
    assert rows[-1]["out"] is None  # bad jpeg -> loader fails -> null
    assert all(len(r["out"]) == 2 for r in rows[:-1])


def test_tf_image_transformer_4channel_keeps_alpha_last(image_df):
    """RGBA model output must become BGRA in the struct (alpha stays the
    LAST channel — CV_32FC4 convention), not ABGR (ADVICE round 1)."""
    from sparkdl_tpu.image.schema import imageStructToArray

    def add_alpha(v, x):
        import jax.numpy as jnp

        rgb = x.astype("float32")
        alpha = jnp.full_like(rgb[..., :1], 99.0)
        return jnp.concatenate([rgb, alpha], axis=-1)

    mf = ModelFunction(fn=add_alpha, variables={})
    t = TFImageTransformer(inputCol="image", outputCol="out",
                           modelFunction=mf, inputSize=[16, 16],
                           outputMode="image", batchSize=8)
    rows = t.transform(image_df).collect()
    vals = [r for r in rows if r["out"] is not None]
    assert len(vals) == 3
    for r in vals:
        arr = imageStructToArray(r["out"])  # BGRA float32
        assert arr.shape[-1] == 4
        # alpha must be the last channel, everywhere 99
        np.testing.assert_allclose(arr[..., 3], 99.0)
        assert not np.allclose(arr[..., 0], 99.0)  # not ABGR


def test_image_mode_packs_outputs_incrementally(fixture_images, monkeypatch):
    """VERDICT r2 weak #5: outputMode="image" must emit structs per engine
    chunk, not concatenate the whole output first: (a) structurally, the
    concatenate-everything path (_run_streaming) is never entered; (b)
    behaviorally, packing of early chunks happens while later chunks are
    still being decoded — O(chunk) residency."""
    import time

    import pyarrow as pa
    import pyarrow.compute as pc

    from sparkdl_tpu.frame import DataFrame

    events = []
    real_s2b = ni.arrowStructsToBatch
    real_a2s = ni.imageArrayToStruct

    def spy_decode(column, h, w, **kw):
        # slow the producer so interleaving is deterministic: the consumer
        # packs chunk 1 long before the serial decode of chunk 6 starts
        time.sleep(0.05)
        events.append("decode")
        return real_s2b(column, h, w, **kw)

    def spy_pack(arr, origin=""):
        events.append("pack")
        return real_a2s(arr, origin=origin)

    monkeypatch.setattr(ni, "arrowStructsToBatch", spy_decode)
    monkeypatch.setattr(ni, "imageArrayToStruct", spy_pack)

    def fail_run_streaming(*a, **kw):
        raise AssertionError(
            "image mode must stream per chunk, not concatenate via "
            "_run_streaming")

    monkeypatch.setattr(TFImageTransformer, "_run_streaming",
                        fail_run_streaming)

    # 48 decodable rows, batchSize 2 (rounds to 8 on the 8-dev mesh) -> 6
    # decode chunks; the engine window (2) + prefetch (2) hold at most ~4
    # chunks before the first output is yielded.
    base = readImages(fixture_images["dir"])
    good = base.table.filter(
        pc.invert(pc.is_null(base.table.column("image"))))
    reps = pa.concat_tables([good] * 16).combine_chunks()
    df = DataFrame(reps)
    mf = ModelFunction(fn=lambda v, x: x.astype("float32") * v["s"],
                       variables={"s": np.float32(1.0)})
    t = TFImageTransformer(inputCol="image", outputCol="out",
                           modelFunction=mf, inputSize=[16, 16],
                           outputMode="image", batchSize=2)
    rows = t.transform(df).collect()
    assert sum(1 for r in rows if r["out"] is not None) == 48
    decode_positions = [i for i, e in enumerate(events) if e == "decode"]
    pack_positions = [i for i, e in enumerate(events) if e == "pack"]
    assert len(decode_positions) == 6
    assert len(pack_positions) == 48
    assert pack_positions[0] < decode_positions[-1], (
        f"first pack must precede last decode (interleaved streaming); "
        f"events: {events[:40]}")


def test_zoo_engine_bf16_env_knob(fake_resnet, image_df, monkeypatch):
    """SPARKDL_ZOO_COMPUTE_DTYPE=bfloat16 keeps the featurizer contract
    (f32 feature vectors, same values within bf16 tolerance)."""
    df = image_df
    ft = DeepImageFeaturizer(inputCol="image", outputCol="features",
                             modelName="ResNet50", batchSize=8)
    base = [r["features"] for r in ft.transform(df).collect()]
    monkeypatch.setenv("SPARKDL_ZOO_COMPUTE_DTYPE", "bfloat16")
    bf16 = [r["features"] for r in ft.transform(df).collect()]
    assert len(base) == len(bf16)
    for a, b in zip(base, bf16):
        if a is None:
            assert b is None
            continue
        a, b = np.asarray(a), np.asarray(b)
        scale = max(1.0, float(np.abs(a).max()))
        assert np.abs(a - b).max() / scale < 0.05  # bf16 compute tolerance
    # the engine itself must hand back f32 (the output_host_dtype cast),
    # not raw bf16 — the one property the knob's plumbing guarantees
    eng = ni._zoo_engine("ResNet50", True, 8)
    out = eng(np.zeros((3, 8, 8, 3), np.uint8))
    assert out.dtype == np.float32
    # unknown dtype values are rejected, not silently f32
    monkeypatch.setenv("SPARKDL_ZOO_COMPUTE_DTYPE", "float16")
    with pytest.raises(ValueError, match="not supported"):
        ni._zoo_engine("ResNet50", True, 8)


def _echo_function(seen):
    import jax.numpy as jnp

    def fn(v, t):
        seen.append(t.dtype)
        return jnp.asarray(t, jnp.float32).sum(axis=1, keepdims=True) * v["w"]

    return ModelFunction(fn=fn, variables={"w": np.float32(2.0)})


@pytest.mark.parametrize("values,arrow,reaches", [
    ([[1, 2, 3], [4, 5, 6], [7, 8, 9]], pa.int32(), np.int32),
    ([[1, 2, 3], [4, 5, 6], [7, 8, 9]], pa.int16(), np.int16),
    ([[1.5, 2.0], [3.0, 4.5], [0.5, 0.25]], pa.float64(), np.float32),
    ([[1.5, 2.0], [3.0, 4.5], [0.5, 0.25]], pa.float32(), np.float32),
])
def test_model_transformer_input_dtype(values, arrow, reaches):
    """An integer list column (token ids) reaches the function as the
    integers it holds; a float column still as float32, whatever its
    width (``KerasTransformer``'s contract)."""
    seen = []
    df = DataFrame(pa.table({"x": pa.array(values, pa.list_(arrow))}))
    mt = ModelTransformer(inputCol="x", outputCol="out",
                          modelFunction=_echo_function(seen), batchSize=2)
    got = mt.transform(df).column_to_numpy("out")
    assert set(seen) == {np.dtype(reaches)}
    np.testing.assert_allclose(got[:, 0], 2 * np.asarray(values).sum(axis=1))


def test_model_transformer_spans_and_engine():
    """``transform`` opens the spans the image stages open, under the
    same names, and ``engine()`` is the engine it ran on."""
    from sparkdl_tpu import obs
    from sparkdl_tpu.parallel.engine import get_cached_engine

    mf = _echo_function([])
    df = DataFrame(pa.table({"x": pa.array(
        [[1, 2, 3, 4]] * 5, pa.list_(pa.int32()))}))
    mt = ModelTransformer(inputCol="x", outputCol="out", modelFunction=mf,
                          batchSize=2)
    obs.trace.configure(enabled=True, capacity=4096)
    try:
        mt.transform(df)
        spans = {s["name"]: s for s in obs.trace.get_tracer().snapshot()}
    finally:
        obs.trace.configure(enabled=False)
    run, pack_in, pack_out = (spans[n] for n in (
        "transform.run", "transform.pack_in", "transform.pack_out"))
    assert run["attrs"] == {"batch_size": 2, "rows": 5, "tokens": 20}
    assert pack_in["attrs"] == {"rows": 5, "bytes": 5 * 4 * 4}
    assert pack_out["attrs"] == {"rows": 5, "values": 5, "bytes": 5 * 4,
                                 "null_rows": 0, "py_values": 0}
    for child in (pack_in, pack_out, spans["engine.call"]):
        assert child["parent_id"] == run["span_id"]
    eng = mt.engine()
    assert eng is get_cached_engine(mt, mf, device_batch_size=2)
    assert eng.metrics.snapshot_raw()["counters"]["engine.rows"] == 5
