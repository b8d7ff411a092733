"""Model-zoo parity tests.

The reference's core correctness oracle is tolerance-based parity against a
local Keras/TF run (``python/tests/transformers/named_image_test.py``,
``python/tests/graph/test_pieces.py``).  Same here: each flax zoo model,
loaded with weights imported from its keras.applications twin, must produce
the same logits as Keras (CPU, float32) within tolerance.

BN statistics are randomized before import so the running mean/var import
path is actually binding (fresh Keras BN stats are identity and would hide
bugs).
"""

import numpy as np
import pytest

from sparkdl_tpu.models import (SUPPORTED_MODELS, get_model_spec,
                                import_keras_weights)


def _keras():
    import keras
    return keras


def _build_keras(spec):
    keras = _keras()
    builder = getattr(keras.applications, spec.keras_app)
    # classifier_activation=None: compare logits, which is a binding test
    # even with O(1)-magnitude random weights (softmax of tiny logits would
    # compare near-uniform vectors and hide errors).
    return builder(weights=None, classifier_activation=None)


def _randomize_bn(model, rng):
    """Give BatchNorm (and EfficientNet's input Normalization) layers
    non-trivial statistics so the import is exercised, not defaults."""
    for layer in model.layers:
        tname = type(layer).__name__
        if tname == "Normalization":
            w = layer.get_weights()
            if w:  # [mean, variance, (count)]
                layer.set_weights(
                    [rng.normal(0.0, 0.1, size=w[0].shape).astype("float32"),
                     rng.uniform(0.5, 1.5, size=w[1].shape).astype("float32")]
                    + list(w[2:]))
            continue
        if tname != "BatchNormalization":
            continue
        new = []
        for w in layer.weights:
            shape = w.shape
            n = w.name if hasattr(w, "name") else ""
            if "moving_variance" in n or "variance" in n:
                new.append(rng.uniform(0.5, 1.5, size=shape).astype("float32"))
            elif "moving_mean" in n or "mean" in n:
                new.append(rng.normal(0.0, 0.1, size=shape).astype("float32"))
            elif "gamma" in n:
                new.append(rng.uniform(0.8, 1.2, size=shape).astype("float32"))
            else:  # beta
                new.append(rng.normal(0.0, 0.1, size=shape).astype("float32"))
        layer.set_weights(new)


# Tier-1 time budget (ISSUE 11 satellite; extended by ISSUE 13): a
# model family's shape and keras-parity contracts are identical block
# structure at different depths, so the DEEPEST twins — the heaviest
# calls in the whole tier-1 suite — carry the `slow` mark while the
# cheapest member keeps the family inside the tier-1 gate, and
# run-tests.sh's full pass (no `-m` filter) still runs the deep twins
# on every gate.  ResNet101/152 (~111s, ISSUE 11): ResNet50 stays
# tier-1.  VGG19 (~72s, ISSUE 13 — the next-heaviest offender by the
# --durations profile): VGG16 stays tier-1 and differs from VGG19 only
# by three repeated conv3 blocks.
_DEEP_TWINS = ("ResNet101", "ResNet152", "VGG19")


def _budgeted(models):
    return [pytest.param(n, marks=pytest.mark.slow)
            if n in _DEEP_TWINS else n for n in models]


@pytest.mark.parametrize("name", _budgeted(SUPPORTED_MODELS))
def test_logit_parity_vs_keras(name):
    spec = get_model_spec(name)
    keras_model = _build_keras(spec)
    rng = np.random.default_rng(42)
    _randomize_bn(keras_model, rng)

    h, w = spec.input_size
    x = rng.normal(0.0, 1.0, size=(2, h, w, 3)).astype("float32")
    ref = np.asarray(keras_model.predict(x, verbose=0))

    module = spec.build()
    # Shape-only template: the import must fill every leaf (load_model path).
    variables = import_keras_weights(
        name, keras_model, spec.abstract_variables())
    import jax
    apply = jax.jit(lambda v, x: module.apply(v, x, train=False, logits=True))
    got = np.asarray(apply(variables, x))

    assert got.shape == ref.shape == (2, 1000)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=2e-3)


@pytest.mark.parametrize("name", _budgeted(SUPPORTED_MODELS))
def test_feature_cut_shape(name):
    spec = get_model_spec(name)
    module = spec.build()
    variables = spec.init_variables()
    h, w = spec.input_size
    x = np.zeros((1, h, w, 3), dtype="float32")
    import jax
    feats = jax.jit(
        lambda v, x: module.apply(v, x, train=False, features=True)
    )(variables, x)
    assert feats.shape == (1, spec.feature_size)


def test_preprocess_parity_vs_keras():
    """Our jax preprocess fns match keras.applications.imagenet_utils for
    every mode on uint8-range input."""
    keras = _keras()
    rng = np.random.default_rng(7)
    x = rng.uniform(0, 255, size=(2, 8, 8, 3)).astype("float32")
    for mode in ("tf", "caffe", "torch"):
        ref = keras.applications.imagenet_utils.preprocess_input(
            x.copy(), mode=mode)
        from sparkdl_tpu.models.preprocess import get_preprocess_fn
        got = np.asarray(get_preprocess_fn(mode)(x))
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_unknown_model_rejected():
    with pytest.raises(ValueError, match="Unknown model"):
        get_model_spec("NoSuchNet")


def test_efficientnet_imports_across_repeated_builds():
    """keras auto-suffixes the input Normalization layer name per session
    build ("normalization", "normalization_1", ...); the second import in
    one process must fall back to creation-order matching instead of
    failing by-name (caught live by the round-3 verify drive) — and it
    must import the right VALUES, not just shapes."""
    rng = np.random.default_rng(5)
    spec = get_model_spec("EfficientNetB0")
    for _ in range(2):
        keras_model = _build_keras(spec)
        _randomize_bn(keras_model, rng)
        variables = import_keras_weights(
            "EfficientNetB0", keras_model, spec.abstract_variables())
    norm_layer = next(l for l in keras_model.layers
                      if type(l).__name__ == "Normalization")
    got = variables["batch_stats"]["normalization"]
    np.testing.assert_allclose(
        np.asarray(got["mean"]),
        np.asarray(norm_layer.get_weights()[0]).reshape(-1))
    np.testing.assert_allclose(
        np.asarray(got["var"]),
        np.asarray(norm_layer.get_weights()[1]).reshape(-1))


def test_efficientnet_imagenet_rescaling_fixup():
    """EfficientNetB0(weights="imagenet") inserts a WEIGHTLESS extra
    Rescaling(1/sqrt(std)) after Normalization (upstream tf#49930); the
    import fixup must capture it as post_scale — and leave the default 1
    for weights=None builds (which lack the layer)."""
    from sparkdl_tpu.models.efficientnet import efficientnet_import_fixup

    spec = get_model_spec("EfficientNetB0")

    # weights=None build: single Rescaling, post_scale stays 1
    keras_model = _build_keras(spec)
    variables = import_keras_weights(
        "EfficientNetB0", keras_model, spec.abstract_variables())
    variables = efficientnet_import_fixup(keras_model, variables)
    np.testing.assert_allclose(
        np.asarray(variables["batch_stats"]["normalization"]["post_scale"]),
        np.ones(3))

    # simulate the imagenet build's layer list: a second Rescaling carrying
    # the per-channel correction
    class _FakeRescaling:
        pass

    _FakeRescaling.__name__ = "Rescaling"
    scale = [1.0 / np.sqrt(v) for v in (0.229 ** 2, 0.224 ** 2, 0.225 ** 2)]
    r1, r2 = _FakeRescaling(), _FakeRescaling()
    r1.scale, r2.scale = 1.0 / 255.0, scale

    class _FakeModel:
        layers = [r1, r2]

    variables = efficientnet_import_fixup(_FakeModel(), variables)
    np.testing.assert_allclose(
        np.asarray(variables["batch_stats"]["normalization"]["post_scale"]),
        np.asarray(scale, np.float32), rtol=1e-6)


def test_efficientnet_drop_connect():
    """ADVICE r3: stochastic depth is available for fine-tuning (keras
    recipe parity) behind a rate knob: default 0 is identity (no rng
    needed), rate>0 in train mode drops residual branches per sample,
    and inference is unaffected by the knob."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models.efficientnet import EfficientNetB0

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.random((2, 64, 64, 3)) * 255, jnp.float32)

    base = EfficientNetB0(num_classes=5)
    variables = base.init(jax.random.PRNGKey(0), x, train=False)
    out0 = base.apply(variables, x, train=False, features=True)

    sd = EfficientNetB0(num_classes=5, drop_connect_rate=0.9)
    # inference: knob is inert, bit-identical features
    out_inf = sd.apply(variables, x, train=False, features=True)
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out_inf))
    # train mode with rate>0 needs a dropout rng and perturbs the output
    outs = []
    for seed in (1, 2):
        o, _ = sd.apply(variables, x, train=True, features=True,
                        mutable=["batch_stats"],
                        rngs={"dropout": jax.random.PRNGKey(seed)})
        outs.append(np.asarray(o))
    assert not np.allclose(outs[0], outs[1])
    # rate=0 in train mode stays rng-free (the estimator fine-tune path)
    base.apply(variables, x, train=True, features=True,
               mutable=["batch_stats"])


def test_inception_fused_heads_parity():
    """InceptionV3 fused branch heads (one wide 1x1 conv per mixed block
    instead of 2-3 narrow ones, BN folded into the kernel) is the same
    function as the per-branch model on the same variables, with an
    identical variable tree (VERDICT r4 #2 structural lever)."""
    import jax

    from sparkdl_tpu.models.inception import InceptionV3

    base = InceptionV3(fused_heads=False)
    fh = InceptionV3(fused_heads=True)
    rng = np.random.default_rng(3)
    x = ((rng.uniform(0, 255, size=(1, 299, 299, 3)) / 127.5) - 1.0
         ).astype(np.float32)
    v0 = jax.jit(lambda r, xx: base.init(r, xx, train=False))(
        jax.random.PRNGKey(0), x)
    v1 = jax.eval_shape(lambda: fh.init(jax.random.PRNGKey(0), x,
                                        train=False))
    assert (jax.tree_util.tree_structure(v0)
            == jax.tree_util.tree_structure(v1))
    a = np.asarray(jax.jit(lambda v, xx: base.apply(
        v, xx, train=False, features=True))(v0, x))
    b = np.asarray(jax.jit(lambda v, xx: fh.apply(
        v, xx, train=False, features=True))(v0, x))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_resnet_fused_shortcut_parity(monkeypatch):
    """ResNet50's fused shortcut+reduce conv (downsample blocks) is the
    same function as the per-conv model on the same variables, with an
    identical variable tree; the registry env knob gates and keys it."""
    import jax

    from sparkdl_tpu.models import get_model_spec, model_variant_key
    from sparkdl_tpu.models.resnet import ResNet50

    base = ResNet50(num_classes=4, fused_shortcut=False)
    fused = ResNet50(num_classes=4, fused_shortcut=True)
    rng = np.random.default_rng(5)
    x = rng.normal(0, 1, size=(2, 96, 96, 3)).astype(np.float32)
    v0 = jax.jit(lambda r, xx: base.init(r, xx, train=False))(
        jax.random.PRNGKey(0), x)
    v1 = jax.eval_shape(lambda: fused.init(jax.random.PRNGKey(0), x,
                                           train=False))
    assert (jax.tree_util.tree_structure(v0)
            == jax.tree_util.tree_structure(v1))
    a = np.asarray(jax.jit(lambda v, xx: base.apply(
        v, xx, train=False, features=True))(v0, x))
    b = np.asarray(jax.jit(lambda v, xx: fused.apply(
        v, xx, train=False, features=True))(v0, x))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    # train mode takes the plain branch and updates batch_stats
    out, mut = fused.apply(v0, x, train=True, features=True,
                           mutable=["batch_stats"])
    assert "batch_stats" in mut

    spec = get_model_spec("ResNet50")
    monkeypatch.delenv("SPARKDL_RN_FUSED_SHORTCUT", raising=False)
    assert spec.build().fused_shortcut is False   # off until measured
    assert model_variant_key("ResNet50") == ""
    monkeypatch.setenv("SPARKDL_RN_FUSED_SHORTCUT", "1")
    assert spec.build().fused_shortcut is True
    assert model_variant_key("ResNet50") == "fsc"
