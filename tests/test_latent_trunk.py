"""The latent-attention trunk with learned sparse attention
(``sparkdl_tpu.models.latent_trunk``) against the benchmark's plain
reference (``benchmark/reference/deepseek_v32.py``: float32 at
``highest``, the indexer's whole score matrix, ``lax.top_k``, dense
attention masked to the selection) at toy widths on the CPU, on the
reference's seeded weights; the selection that binds and the one that
does not; group-limited routing by hand; the share of a layer against
the whole layer; the counters through ``ModelTransformer``."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import latent_trunk_flops as lf
from benchmark.reference import deepseek_v32 as ref
from sparkdl_tpu.models import expert_trunk as et
from sparkdl_tpu.models import latent_trunk as lt

#: hidden 64; one dense layer (MLP 96), then two expert layers; 4 heads
#: (nope 16, rope 16, v 16), q latent 48, kv latent 32; an indexer of 4
#: heads x 32 keeping the top 8 of rows of 64 positions, so that the
#: selection binds from position 8 on; 8 experts of width 32 held of 16
#: in 4 groups of 4 (2 kept), 4 a token, one shared
TOY = {"name": "toy_latent", "hidden_size": 64, "num_attention_heads": 4,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
       "q_lora_rank": 48, "kv_lora_rank": 32, "index_n_heads": 4,
       "index_head_dim": 32, "index_topk": 8, "intermediate_size": 96,
       "moe_intermediate_size": 32, "n_routed_experts": 8,
       "expert_share": [1, 2], "num_experts_per_tok": 4, "n_group": 4,
       "topk_group": 2, "n_shared_experts": 1, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
       "rms_norm_eps": 1e-6, "rope_theta": 10000,
       "max_position_embeddings": 163840,
       "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 4096,
                        "type": "yarn"},
       "vocab_size": 97, "num_hidden_layers": 3, "first_k_dense_replace": 1,
       "sequence_length": 64}
HIGHEST = jax.lax.Precision.HIGHEST
T = TOY["sequence_length"]


def _changed(config, **keys):
    return {**config, **keys}


@pytest.fixture(scope="module")
def weights():
    return ref.draw_weights(TOY, 5)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 97, (3, T), dtype=np.int32)


@pytest.fixture(scope="module")
def want(weights, ids):
    loads = []
    return ref.forward(TOY, weights, ids, loads=loads), np.stack(loads, 1)


def program_variables(weights, dtype, config=TOY):
    def cast(leaf):
        leaf = jnp.asarray(leaf)
        return leaf if leaf.dtype == jnp.float32 else leaf.astype(dtype)

    return {"embedding": cast(weights.embedding()),
            **lt.stack_layers(lambda i, name: cast(weights.leaf(i, name)),
                              config),
            "final_layernorm": cast(weights.final_layernorm())}


def gap(got, want):
    return float((np.abs(np.asarray(got) - want).max(axis=1)
                  / np.abs(want).max(axis=1)).max())


def test_one_program_stacks_every_weight_as_drawn(weights):
    """``stack_layers`` under one jit holds, bit for bit, what the
    reference draws a weight and a layer at a time: ``gate_proj`` beside
    ``up_proj``, a layer's experts on the leading axis, by kind."""
    stacks = jax.jit(lambda: lt.stack_layers(weights.leaf, TOY))()
    dense = TOY["first_k_dense_replace"]
    held = TOY["n_routed_experts"]
    np.testing.assert_array_equal(
        stacks["dense"]["mlp.gate_up_proj"][0],
        jnp.concatenate([weights.leaf(0, "mlp.gate_proj"),
                         weights.leaf(0, "mlp.up_proj")], axis=-1))
    for layer in range(TOY["num_hidden_layers"]):
        np.testing.assert_array_equal(
            stacks["layers"]["self_attn.kv_b_proj"][layer],
            weights.leaf(layer, "self_attn.kv_b_proj"))
    for i in range(TOY["num_hidden_layers"] - dense):
        np.testing.assert_array_equal(
            stacks["experts"]["mlp.experts.down_proj"][i * held:
                                                       (i + 1) * held],
            weights.leaf(dense + i, "mlp.experts.down_proj"))
        np.testing.assert_array_equal(
            stacks["experts"]["mlp.gate.e_score_correction_bias"][i],
            weights.leaf(dense + i, "mlp.gate.e_score_correction_bias"))


# -- (a) the trunk is the reference ------------------------------------------

@pytest.mark.parametrize("chunk", [T, 16])
def test_float32_trunk_is_the_reference(weights, ids, want, chunk,
                                        tied_by_hand):
    """Float32 at 'highest' over the same numbers, the queries in one
    chunk or four: the order of the sums alone differs.  The queries
    whose selection took the tie passes are those counted by hand."""
    got = lt.apply(program_variables(weights, jnp.float32), ids, TOY,
                   precision=HIGHEST, query_chunk=chunk, token_chunk=64)
    features, load = want
    assert got["features"].shape == (3, 64)
    assert got["features"].dtype == jnp.float32
    assert gap(got["features"], features) < 1e-4
    # two expert layers, eight held experts: the same tokens by expert
    assert got["expert_load"].shape == (3, 2, 8)
    np.testing.assert_array_equal(np.asarray(got["expert_load"]), load)
    counts = np.asarray(got["sparse_counts"])
    assert counts.shape == (3, 3, 4)
    np.testing.assert_array_equal(counts[..., 0], lf.causal_pairs(T))
    np.testing.assert_array_equal(counts[..., 1], lf.selected_pairs(T, 8))
    # the jax.numpy form reads every key for every query
    np.testing.assert_array_equal(counts[..., 2], T * T)
    assert counts[..., 3].sum() == tied_by_hand()


def test_bfloat16_trunk_stays_in_its_band(weights, ids, want):
    """The stated precision against the float32 reference: the rounding
    of bfloat16 and what it flips (at 64 positions a token that takes
    another expert, or a key the indexer ranks on the other side of the
    eighth, is a few percent of the row's mean; a fault reads of order
    1)."""
    got = lt.apply(program_variables(weights, jnp.bfloat16), ids, TOY)
    assert 1e-4 < gap(got["features"], want[0]) < 0.2


def test_every_weight_of_the_layer_moves_the_output(weights, ids, want):
    """No weight is dead: doubling any one of layer 1's (an expert
    layer) moves the features by more than rounding."""
    base = program_variables(weights, jnp.float32)
    for name in ("self_attn.q_b_proj", "self_attn.kv_b_proj",
                 "self_attn.o_proj", "self_attn.indexer.wq_b",
                 "self_attn.indexer.wk", "self_attn.indexer.weights_proj",
                 "self_attn.indexer.k_norm.bias", "self_attn.q_a_layernorm"):
        variables = jax.tree_util.tree_map(lambda v: v, base)
        variables["layers"][name] = variables["layers"][name].at[1].multiply(
            -1.5 if "indexer" in name else 2.0)
        got = lt.apply(variables, ids, TOY, precision=HIGHEST)
        assert gap(got["features"], want[0]) > 1e-3, name


# -- (d) a selection that does not bind is dense latent attention -------------

def test_a_selection_over_every_key_is_dense_attention(ids):
    """With ``index_topk`` at the row's length the indexer chooses every
    causal key, and the layer is plain causal latent attention: the
    indexer's weights then move nothing."""
    config = _changed(TOY, index_topk=T)
    weights = ref.draw_weights(config, 9)
    base = program_variables(weights, jnp.float32, config)
    got = lt.apply(base, ids, config, precision=HIGHEST)
    variables = jax.tree_util.tree_map(lambda v: v, base)
    variables["layers"]["self_attn.indexer.weights_proj"] *= -3.0
    variables["layers"]["self_attn.indexer.wk"] *= 0.5
    other = lt.apply(variables, ids, config, precision=HIGHEST)
    np.testing.assert_allclose(np.asarray(other["features"]),
                               np.asarray(got["features"]), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got["sparse_counts"])[..., 1],
                                  lf.causal_pairs(T))
    # and the plain dense attention, written out here, is what the layer adds
    f32 = jnp.float32
    w = {n: jnp.asarray(weights.leaf(1, n)).astype(f32)
         for n in weights.names(1)}
    x = jax.random.normal(jax.random.PRNGKey(2), (T, 64), f32)
    with jax.default_matmul_precision("highest"):
        dense = _dense_mla(config, w, x)
        layer = {n: v[1] for n, v in base["layers"].items()}
        got_x, _ = lt.attention_row(config, x, layer, f32, HIGHEST)
    np.testing.assert_allclose(np.asarray(got_x - x), np.asarray(dense),
                               atol=1e-4)


def _dense_mla(c, w, x):
    """Causal multi-head latent attention with no selection at all."""
    t, eps = x.shape[0], c["rms_norm_eps"]
    heads, nope, kl = (c["num_attention_heads"], c["qk_nope_head_dim"],
                       c["kv_lora_rank"])
    freq = jnp.asarray(ref.inverse_frequencies(c), jnp.float32)
    at = jnp.arange(t, dtype=jnp.float32)
    h = ref.rms_norm(x, w["input_layernorm"], eps)
    c_q = ref.rms_norm(h @ w["self_attn.q_a_proj"],
                       w["self_attn.q_a_layernorm"], eps)
    q = (c_q @ w["self_attn.q_b_proj"]).reshape(t, heads, -1)
    kv = h @ w["self_attn.kv_a_proj_with_mqa"]
    c_kv = ref.rms_norm(kv[:, :kl], w["self_attn.kv_a_layernorm"], eps)
    k_pe = ref.rotary_pairs(kv[:, kl:], at, freq)
    kvb = (c_kv @ w["self_attn.kv_b_proj"]).reshape(t, heads, -1)
    s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kvb[..., :nope])
         + jnp.einsum("qhd,kd->hqk", ref.rotary_pairs(q[..., nope:], at, freq),
                      k_pe)) * ref.attention_scale(c)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kvb[..., nope:])
    return a.reshape(t, -1) @ w["self_attn.o_proj"]


# -- (e), (g) group-limited routing -------------------------------------------

def test_group_limited_routing_drops_a_top_experts_group():
    """By hand: 8 experts in 4 groups of 2, 2 groups kept, 2 experts a
    token.  Expert 6 has the second-largest score, but its group (6, 7)
    sums less than groups (0, 1) and (2, 3), so the choice is expert 0
    and expert 2 — a flat top-2 would take 0 and 6."""
    config = {"num_experts": 8, "expert_share": [0, 1],
              "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
              "route_norm": True, "route_scale": 2.5}
    logits = jnp.asarray([[2.0, 1.0, 1.5, 1.2, -3.0, -3.0, 1.9, -4.0]])
    router = jnp.eye(8, dtype=jnp.float32)
    chosen, weight = et._route(config, logits, router)
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 2]
    s = jax.nn.sigmoid(logits[0])
    want = 2.5 * np.asarray([s[0], s[2]]) / float(s[0] + s[2])
    np.testing.assert_allclose(np.sort(np.asarray(weight)[0]), np.sort(want),
                               rtol=1e-6)
    flat, _ = et._route({**config, "n_group": 1, "topk_group": 1}, logits,
                        router)
    assert sorted(np.asarray(flat)[0].tolist()) == [0, 6]
    # the reference's own group rule agrees
    w = {"mlp.gate.weight": router,
         "mlp.gate.e_score_correction_bias": jnp.zeros(8)}
    ref_config = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 2,
                  "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    got, _ = ref.route(ref_config, w, logits)
    assert sorted(np.asarray(got)[0].tolist()) == [0, 2]


def test_one_group_routes_as_before_bit_for_bit():
    """``n_group`` 1 (Trinity's configuration) takes the flat top-k, the
    program it always traced: the same experts and weights bit for bit
    as a top-k written here, and no group step in the traced program."""
    config = {"num_experts": 32, "expert_share": [0, 8],
              "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
              "route_norm": True, "route_scale": 2.448}
    tokens = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
    router = jax.random.normal(jax.random.PRNGKey(1), (48, 256)) / 7
    bias = 0.03 * jax.random.normal(jax.random.PRNGKey(2), (256,))
    chosen, weight = et._route(config, tokens, router, bias)
    scores = jax.nn.sigmoid(jnp.dot(tokens, router,
                                    precision=jax.lax.Precision.HIGHEST))
    _, want = jax.lax.top_k(scores + bias, 4)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(want))
    w = jnp.take_along_axis(scores, want, axis=-1)
    np.testing.assert_array_equal(
        np.asarray(weight), np.asarray(2.448 * (w / w.sum(-1, keepdims=True))))
    text = str(jax.make_jaxpr(lambda t: et._route(config, t, router, bias))(
        tokens))
    assert text.count("top_k") == 1


# -- (f) the share -----------------------------------------------------------

SHARES = 2


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """The guide's share test.  Of one expert layer, what the PROGRAM
    gives for each share — its experts' part of the experts' sum — with
    what every chip computes alike (the shared expert) counted once,
    adds up to what the uncut REFERENCE gives for the whole layer's MLP
    branch; the router scores all 16 experts in every share."""
    f32 = jnp.float32
    whole = _changed(TOY, n_routed_experts=16, expert_share=[0, 1])
    weights = ref.draw_weights(whole, 11)
    w = {n: jnp.asarray(weights.leaf(1, n)).astype(f32)
         for n in weights.names(1)}
    h2 = jax.random.normal(jax.random.PRNGKey(3), (T, 64), f32)
    steps = ref._steps(json.dumps(whole, sort_keys=True), None)
    with jax.default_matmul_precision("highest"):
        m, chosen, weight = steps["shared"](w, h2)
        for local in range(16):
            tokens = np.flatnonzero((np.asarray(chosen) == local).any(1))
            if len(tokens):
                m = steps["expert"](w, m, h2, chosen, weight, local,
                                    tokens, len(tokens))
    whole_m = np.asarray(m)
    routed, took = [], []
    for index in range(SHARES):
        config = _changed(whole, n_routed_experts=8,
                          expert_share=[index, SHARES])
        held = slice(8 * index, 8 * (index + 1))
        gate_up = jnp.concatenate([w["mlp.experts.gate_proj"][held],
                                   w["mlp.experts.up_proj"][held]], -1)
        route = lt.routing(config)
        chosen, weight = et._route(route, h2, w["mlp.gate.weight"],
                                   w["mlp.gate.e_score_correction_bias"])
        part, load, _ = et._held_experts(
            route, h2[None], chosen, weight, gate_up,
            w["mlp.experts.down_proj"][held], 0, f32, HIGHEST)
        routed.append(part[0])
        took.append(np.asarray(load))
    shared = et._gated_mlp(
        h2, jnp.concatenate([w["mlp.shared_experts.gate_proj"],
                             w["mlp.shared_experts.up_proj"]], -1),
        w["mlp.shared_experts.down_proj"], f32, HIGHEST)
    np.testing.assert_allclose(np.asarray(sum(routed) + shared), whole_m,
                               atol=1e-4)
    # every token's four experts are somebody's: nothing is lost between
    assert np.concatenate(took, axis=1).sum() == T * 4
    assert np.abs(np.asarray(routed[0] + shared) - whole_m).max() > 1e-2


# -- through the stage: the column and the counters ---------------------------

def test_model_function_through_model_transformer(weights, ids, want):
    """The cell's path: an int32 list column through ``ModelTransformer``
    over ``model_function``; ``features`` becomes the column,
    ``expert_load`` and ``sparse_counts`` the engine's counters, of the
    real rows only."""
    import pyarrow as pa

    from sparkdl_tpu import ModelTransformer
    from sparkdl_tpu.frame import DataFrame

    mf = lt.model_function(TOY, program_variables(weights, jnp.bfloat16),
                           compute_dtype="float32",
                           matmul_precision="highest")
    assert mf.counter_names == ("expert_load", "sparse_counts")
    frame = DataFrame(pa.table({"tokens": pa.array(
        list(ids), pa.list_(pa.int32()))}))
    stage = ModelTransformer(inputCol="tokens", outputCol="features",
                             modelFunction=mf, batchSize=2)
    out = stage.transform(frame)
    got = out.column_to_numpy("features")
    assert got.shape == (3, 64) and gap(got, want[0]) < 1e-4
    counters = stage.engine().metrics.snapshot_raw()["counters"]
    assert counters["engine.rows"] == 3 and counters["engine.pad_rows"] >= 1
    assert counters["moe.pairs"] == want[1].sum()
    layers = TOY["num_hidden_layers"]
    assert counters["sparse.indexed_pairs"] == 3 * layers * lf.causal_pairs(T)
    assert counters["sparse.selected_pairs"] == \
        3 * layers * lf.selected_pairs(T, 8)
    assert counters["sparse.fetched_keys"] == 3 * layers * T * T
    assert 0 <= counters["sparse.tied_queries"] <= 3 * layers * T


def test_the_count_of_operations_by_hand():
    """``latent_trunk_flops`` at the toy's widths, counted here term by
    term: a dense layer's and an expert layer's products at every
    position, the routed experts at 2 of 4 a token, the indexer at the
    causal pairs and the attention at the selected pairs."""
    c = {**TOY, "sequence_length": T}
    attention = (64 * 48 + 48 * 4 * 32 + 64 * 48 + 32 * 4 * 32 + 4 * 16 * 64
                 + 48 * 4 * 32 + 64 * 32 + 64 * 4)
    dense = attention + 3 * 64 * 96
    expert = attention + 64 * 16 + 3 * 64 * 32 + 2 * 3 * 64 * 32
    pairs = 3 * (lf.causal_pairs(T) * 2 * 4 * 33
                 + (36 + (T - 8) * 8) * 2 * 4 * (32 + 16 + 32))
    assert lf.selected_pairs(T, 8) == 36 + (T - 8) * 8
    assert lf.flops_per_row(c) == 2 * T * (dense + 2 * expert) + pairs
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "deepseek_v3_2_exp.json")) as fh:
        published = json.load(fh)
    assert published["flops_per_image"] == lf.flops_per_row(published)
