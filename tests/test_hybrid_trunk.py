"""The hybrid trunk (``sparkdl_tpu.models.hybrid_trunk``) against the
benchmark's plain reference (``benchmark/reference/falcon_h1.py``:
float32 at ``highest``, full softmax, the recurrence token by token) at
toy widths on the CPU, on the reference's seeded weights."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falcon_h1 as ref
from sparkdl_tpu.models import hybrid_trunk as ht

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "falcon_h1_34b.json")) as _fh:
    PUBLISHED = json.load(_fh)

#: hidden 64, 2 blocks, 4 query / 2 key-value heads of 16, 4 mixer heads
#: of 16 in 2 groups, state 16, chunk 8, 32 positions, 97 ids; every
#: multiplier as published
TOY = {**PUBLISHED, "hidden_size": 64, "intermediate_size": 128,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
       "mamba_n_groups": 2, "mamba_d_state": 16, "mamba_chunk_size": 8,
       "vocab_size": 97, "num_hidden_layers": 2, "sequence_length": 32}
HIGHEST = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def weights():
    return ref.draw_weights(TOY, 5)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 97, (3, 32), dtype=np.int32)


@pytest.fixture(scope="module")
def want(weights, ids):
    return ref.forward(TOY, weights, ids)


def program_variables(weights, dtype):
    cast = lambda leaf: jnp.asarray(leaf).astype(dtype)  # noqa: E731
    return {"embedding": cast(weights.embedding()),
            "blocks": ht.stack_blocks(
                lambda i, name: cast(weights.leaf(i, name)), 2),
            "final_layernorm": cast(weights.final_layernorm())}


def gap(got, want):
    return float((np.abs(np.asarray(got) - want).max(axis=1)
                  / np.abs(want).max(axis=1)).max())


def test_float32_trunk_is_the_reference(weights, ids, want):
    got = ht.apply(program_variables(weights, jnp.float32), ids, TOY,
                   precision=HIGHEST)
    assert got.shape == (3, 64) and got.dtype == jnp.float32
    # both are float32 at 'highest' over the same numbers: the order of
    # the sums alone differs (chunks, blocked softmax)
    assert gap(got, want) < 1e-4


def test_bfloat16_trunk_stays_in_its_band(weights, ids, want):
    """bfloat16 operands, float32 accumulation: 2**-8 a product, two
    blocks deep, averaged over 32 positions: a few thousandths of the
    feature scale — and far from the reference held in int8."""
    got = ht.apply(program_variables(weights, jnp.bfloat16), ids, TOY)
    assert got.dtype == jnp.float32
    assert 1e-4 < gap(got, want) < 0.015
    control = ref.forward(TOY, weights, ids, operands="int8")
    assert gap(control, want) > 0.015


@pytest.mark.parametrize("name,index", [
    (name, None) if not isinstance(PUBLISHED[name], list) else (name, i)
    for name in ht.MULTIPLIERS if name != "attention_in_multiplier"
    for i in range(len(PUBLISHED[name])
                   if isinstance(PUBLISHED[name], list) else 1)])
def test_every_multiplier_changes_the_output(weights, ids, want, name, index):
    """Left at 1, each published multiplier moves the features by far
    more than the parity band: none is dropped, none applied where it
    cancels.  (``attention_in_multiplier`` is published as 1: see the
    next test.)"""
    config = dict(TOY)
    if index is None:
        config[name] = 1.0
    else:
        config[name] = [1.0 if i == index else m
                        for i, m in enumerate(TOY[name])]
    variables = program_variables(weights, jnp.float32)
    got = ht.apply(variables, ids, config, precision=HIGHEST)
    assert gap(got, want) > 1e-3
    # and the reference applies it in the same place
    assert gap(got, ref.forward(config, weights, ids)) < 1e-4


def test_attention_in_multiplier_is_applied(weights, ids):
    config = dict(TOY, attention_in_multiplier=0.5)
    variables = program_variables(weights, jnp.float32)
    got = ht.apply(variables, ids, config, precision=HIGHEST)
    assert gap(got, ref.forward(config, weights, ids)) < 1e-4
    assert gap(got, ref.forward(TOY, weights, ids)) > 1e-3


def test_positions_do_not_see_the_ones_after_them(weights, ids, monkeypatch):
    """Changing id ``j`` leaves every position before ``j`` as it was:
    read before the pooling, which mixes the positions."""
    seen = {}
    real = ht._rms_norm

    def keep_last(x, scale, eps, groups=1):
        out = real(x, scale, eps, groups)
        seen["f"] = out                 # the final norm is the last call
        return out

    monkeypatch.setattr(ht, "_rms_norm", keep_last)
    variables = program_variables(weights, jnp.float32)
    j = 20
    ht.apply(variables, ids, TOY, precision=HIGHEST)
    before = np.asarray(seen["f"])
    changed = ids.copy()
    changed[:, j] = (changed[:, j] + 1) % 97
    ht.apply(variables, changed, TOY, precision=HIGHEST)
    after = np.asarray(seen["f"])
    np.testing.assert_array_equal(after[:, :j], before[:, :j])
    assert np.abs(after[:, j:] - before[:, j:]).max() > 1e-3
    # and every later position moved: the mixer's state and the
    # attention both carry position j forward
    assert (np.abs(after[:, j:] - before[:, j:]).max(axis=2) > 0).all()


def test_init_gives_the_trunks_own_tree():
    variables = ht.init(TOY, jax.random.PRNGKey(0))
    shapes = ht.block_shapes(TOY)
    assert set(variables["blocks"]) == set(shapes)
    for name, shape in shapes.items():
        assert variables["blocks"][name].shape == (2,) + shape
        assert variables["blocks"][name].dtype == jnp.bfloat16
    out = ht.apply(variables, np.zeros((2, 32), np.int32), TOY)
    assert out.shape == (2, 64) and bool(jnp.isfinite(out).all())


def test_model_function_through_model_transformer(weights, ids, want):
    """The one path the benchmark's cell uses: an int32 list column
    through ``ModelTransformer`` over ``model_function``."""
    import pyarrow as pa

    from sparkdl_tpu import ModelTransformer
    from sparkdl_tpu.frame import DataFrame

    mf = ht.model_function(TOY, program_variables(weights, jnp.bfloat16),
                           compute_dtype="float32",
                           matmul_precision="highest")
    assert mf.variables["embedding"].dtype == jnp.float32
    frame = DataFrame(pa.table({"tokens": pa.array(
        list(ids), pa.list_(pa.int32()))}))
    stage = ModelTransformer(inputCol="tokens", outputCol="features",
                             modelFunction=mf, batchSize=2)
    got = stage.transform(frame).column_to_numpy("features")
    assert got.shape == (3, 64) and gap(got, want) < 1e-4
    assert stage.engine().metrics.snapshot_raw()["counters"][
        "engine.rows"] == 3
