"""The sparse-expert trunk (``sparkdl_tpu.models.expert_trunk``) against
the benchmark's plain reference (``benchmark/reference/trinity.py``:
float32 at ``highest``, the mask written out, an expert over the tokens
a boolean mask picks) at toy widths on the CPU, on the reference's
seeded weights; the share of a layer against the whole layer; the
routing's load as counters."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import trinity as ref
from sparkdl_tpu.models import expert_trunk as et
from sparkdl_tpu.ops import grouped_matmul as gm

#: hidden 64; one dense layer (MLP 160), then a sliding, a full and a
#: sliding expert layer; 4 query / 2 key-value heads of 16; 8 experts of
#: width 48 held of 16, 4 a token, one shared; window 8 of 32 positions
TOY = {"name": "toy_experts", "hidden_size": 64, "head_dim": 16,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 160, "moe_intermediate_size": 48,
       "num_experts": 8, "expert_share": [1, 2], "num_experts_per_tok": 4,
       "num_shared_experts": 1, "route_norm": True, "route_scale": 2.448,
       "score_func": "sigmoid", "sliding_window": 8, "rope_theta": 10000,
       "rms_norm_eps": 1e-5, "mup_enabled": True, "vocab_size": 97,
       "num_hidden_layers": 4, "num_dense_layers": 1,
       "layer_types": ["sliding_attention", "sliding_attention",
                       "full_attention", "sliding_attention"],
       "sequence_length": 32}
HIGHEST = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def weights():
    return ref.draw_weights(TOY, 5)


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 97, (3, 32), dtype=np.int32)


@pytest.fixture(scope="module")
def want(weights, ids):
    loads = []
    return ref.forward(TOY, weights, ids, loads=loads), np.stack(loads, 1)


def program_variables(weights, dtype, config=TOY):
    def cast(leaf):
        leaf = jnp.asarray(leaf)
        return leaf if leaf.dtype == jnp.float32 else leaf.astype(dtype)

    return {"embedding": cast(weights.embedding()),
            **et.stack_layers(lambda i, name: cast(weights.leaf(i, name)),
                              config),
            "final_layernorm": cast(weights.final_layernorm())}


def gap(got, want):
    return float((np.abs(np.asarray(got) - want).max(axis=1)
                  / np.abs(want).max(axis=1)).max())


def test_float32_trunk_is_the_reference(weights, ids, want):
    got = et.apply(program_variables(weights, jnp.float32), ids, TOY,
                   precision=HIGHEST)
    features, load = want
    assert got["features"].shape == (3, 64)
    assert got["features"].dtype == jnp.float32
    # float32 at 'highest' over the same numbers: the order of the sums
    # alone differs (blocked softmax, experts' sum slot by slot)
    assert gap(got["features"], features) < 1e-4
    # three expert layers, eight held experts: the same tokens by expert
    assert got["expert_load"].shape == (3, 3, 8)
    np.testing.assert_array_equal(np.asarray(got["expert_load"]), load)
    # four of sixteen a token, eight held: about half of 3 x 3 x 32 x 4
    assert 0.35 < load.sum() / (3 * 3 * 32 * 4) < 0.65


def test_bfloat16_trunk_stays_in_its_band(weights, ids, want):
    """bfloat16 operands, float32 accumulation, four layers deep: at 32
    positions a few hundredths of the feature scale — a token whose
    fourth and fifth scores lie within bfloat16's rounding changes
    expert, and a mean over 32 positions hides little of it (0.02-0.07
    on three seeds; the cell's mean is over 16,384) — and the reference
    in int8 twice as far (0.14-0.22)."""
    got = et.apply(program_variables(weights, jnp.bfloat16), ids, TOY)
    assert got["features"].dtype == jnp.float32
    assert 1e-4 < gap(got["features"], want[0]) < 0.1
    control = ref.forward(TOY, weights, ids, operands="int8")
    assert gap(control, want[0]) > 0.12


def _changed(config, **keys):
    return {**config, **keys}


@pytest.mark.parametrize("change", [
    {"sliding_window": 32},
    {"layer_types": ["sliding_attention"] * 4},
    {"layer_types": ["full_attention"] * 4},
    {"route_scale": 1.0}, {"route_norm": False}, {"mup_enabled": False},
    {"expert_share": [0, 2]}, {"num_experts_per_tok": 2},
], ids=lambda change: next(iter(change)) + "=" + str(
    next(iter(change.values())))[:12])
def test_every_key_of_the_layer_changes_the_output(weights, ids, want,
                                                   change):
    """Each key moves the features by far more than the parity band, and
    the reference reads it in the same place."""
    config = _changed(TOY, **change)
    got = et.apply(program_variables(weights, jnp.float32), ids, config,
                   precision=HIGHEST)["features"]
    assert gap(got, want[0]) > 1e-3
    assert gap(got, ref.forward(config, weights, ids)) < 1e-4


def test_the_bias_chooses_and_does_not_weigh(weights, ids, want):
    """With the bias left out other experts are chosen; with the bias
    added to the WEIGHTS the features would move too: the reference
    keeps it to the choice, and the program agrees with the reference."""
    variables = program_variables(weights, jnp.float32)
    bias = variables["experts"]["mlp.expert_bias"]
    variables["experts"]["mlp.expert_bias"] = jnp.zeros_like(bias)
    got = et.apply(variables, ids, TOY, precision=HIGHEST)
    assert gap(got["features"], want[0]) > 1e-3
    assert (np.asarray(got["expert_load"]) != want[1]).any()


def test_positions_do_not_see_the_ones_after_them(weights, ids, monkeypatch):
    """Changing id ``j`` leaves every position before ``j`` as it was,
    and a sliding layer's window does not reach the last position from
    the first: read before the pooling, which mixes the positions."""
    seen = {}
    real = et._rms_norm

    def keep_last(x, scale, eps):
        out = real(x, scale, eps)
        seen["f"] = out                 # the final norm is the last call
        return out

    monkeypatch.setattr(et, "_rms_norm", keep_last)
    variables = program_variables(weights, jnp.float32)
    j = 20
    et.apply(variables, ids, TOY, precision=HIGHEST)
    before = np.asarray(seen["f"])
    changed = ids.copy()
    changed[:, j] = (changed[:, j] + 1) % 97
    et.apply(variables, changed, TOY, precision=HIGHEST)
    after = np.asarray(seen["f"])
    np.testing.assert_array_equal(after[:, :j], before[:, :j])
    assert (np.abs(after[:, j:] - before[:, j:]).max(axis=2) > 0).all()
    # all layers sliding, window 8, four layers: position 0 reaches 28
    sliding = _changed(TOY, layer_types=["sliding_attention"] * 4)
    et.apply(variables, ids, sliding, precision=HIGHEST)
    before = np.asarray(seen["f"])
    changed = ids.copy()
    changed[:, 0] = (changed[:, 0] + 1) % 97
    et.apply(variables, changed, sliding, precision=HIGHEST)
    moved = np.abs(np.asarray(seen["f"]) - before).max(axis=(0, 2))
    assert (moved[:29] > 0).all() and (moved[29:] == 0).all()


# -- the share ----------------------------------------------------------------

SHARES = 8
#: one expert layer, uncut: sixteen query and eight key/value heads of 8,
#: all sixteen experts; a share holds two, one and two of them
WHOLE = _changed(TOY, num_hidden_layers=1, num_dense_layers=0,
                 layer_types=["sliding_attention"], num_experts=16,
                 expert_share=[0, 1], num_attention_heads=16,
                 num_key_value_heads=8, head_dim=8)


def _share_of(whole_layer, index):
    """Share ``index``: query heads 2i, 2i+1 with key/value head i and the
    matching rows of ``o_proj``, experts 2i, 2i+1; what every chip holds
    alike (norms, router, bias, shared expert) whole."""
    hd = WHOLE["head_dim"]
    q = slice(2 * index * hd, 2 * (index + 1) * hd)
    kv = slice(index * hd, (index + 1) * hd)
    e = slice(2 * index, 2 * (index + 1))
    cut = {"self_attn.q_proj": (..., q), "self_attn.gate_proj": (..., q),
           "self_attn.k_proj": (..., kv), "self_attn.v_proj": (..., kv),
           "self_attn.o_proj": (q,), "mlp.experts.gate_proj": (e,),
           "mlp.experts.up_proj": (e,), "mlp.experts.down_proj": (e,)}
    return {name: leaf[cut[name]] if name in cut else leaf
            for name, leaf in whole_layer.items()}


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """The guide's share test.  Of one expert layer, what the PROGRAM
    gives for each of the eight shares — its heads' part of ``o_proj``'s
    sum, its experts' part of the experts' sum — with the shared expert
    counted once, adds up to what the uncut REFERENCE gives for the whole
    layer: both branches before the norm that follows each (the
    deployment sums the parts, then norms)."""
    f32 = jnp.float32
    weights = ref.draw_weights(WHOLE, 11)
    w = {n: jnp.asarray(v).astype(f32) for n, v in weights.layer(0).items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64), f32)
    eps = WHOLE["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        whole_a = jnp.stack([ref.attention_branch(
            WHOLE, w, row, "sliding_attention") for row in x])
        h2 = ref.rms_norm(x + ref.rms_norm(
            whole_a, w["post_attention_layernorm"], eps),
            w["pre_mlp_layernorm"], eps)
        whole_m, whole_load = ref.expert_branch(
            WHOLE, w, h2, ref._steps(json.dumps(WHOLE, sort_keys=True),
                                     "sliding_attention", None))
    cut = _changed(WHOLE, num_experts=2, num_attention_heads=2,
                   num_key_value_heads=1)
    attention, routed, load = [], [], []
    for index in range(SHARES):
        config = _changed(cut, expert_share=[index, SHARES])
        share = _share_of(w, index)
        stacks = et.stack_layers(lambda _, name: share[name], config)
        layer = {n: leaf[0] for n, leaf in stacks["layers"].items()}
        attention.append(et._attention_branch(config, x, layer, True, f32,
                                              HIGHEST))
        experts = stacks["experts"]
        chosen, weight = et._route(config, h2.reshape(-1, 64),
                                   experts["mlp.router.gate"][0],
                                   experts["mlp.expert_bias"][0])
        part, took, _ = et._held_experts(
            config, h2, chosen, weight, experts["mlp.experts.gate_up_proj"],
            experts["mlp.experts.down_proj"], 0, f32, HIGHEST)
        routed.append(part)
        load.append(np.asarray(took))
    # what every chip computes alike, once
    shared = et._gated_mlp(h2, experts["mlp.shared_experts.gate_up_proj"][0],
                           experts["mlp.shared_experts.down_proj"][0], f32,
                           HIGHEST)
    np.testing.assert_allclose(np.asarray(sum(attention)),
                               np.asarray(whole_a), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sum(routed) + shared),
                               np.asarray(whole_m), atol=1e-4)
    np.testing.assert_array_equal(np.concatenate(load, axis=1), whole_load)
    # every token's four experts are somebody's: nothing is lost between
    assert whole_load.sum() == 2 * 32 * WHOLE["num_experts_per_tok"]
    # and one share alone is not the layer
    assert np.abs(np.asarray(routed[0] + shared - whole_m)).max() > 1e-2


# -- through the stage: the column and the counters ---------------------------

def test_model_function_through_model_transformer(weights, ids, want):
    """The one path the benchmark's cell uses: an int32 list column
    through ``ModelTransformer`` over ``model_function``; ``features``
    becomes the column, ``expert_load`` the engine's counters, exactly
    the reference's count of pairs on the same ids, padding trimmed."""
    import pyarrow as pa

    from sparkdl_tpu import ModelTransformer
    from sparkdl_tpu.frame import DataFrame

    mf = et.model_function(TOY, program_variables(weights, jnp.bfloat16),
                           compute_dtype="float32",
                           matmul_precision="highest")
    assert mf.counter_names == ("expert_load",)
    assert mf.variables["embedding"].dtype == jnp.float32
    frame = DataFrame(pa.table({"tokens": pa.array(
        list(ids), pa.list_(pa.int32()))}))
    stage = ModelTransformer(inputCol="tokens", outputCol="features",
                             modelFunction=mf, batchSize=2)
    out = stage.transform(frame)
    assert out.columns == ["tokens", "features"]
    got = out.column_to_numpy("features")
    assert got.shape == (3, 64) and gap(got, want[0]) < 1e-4
    counters = stage.engine().metrics.snapshot_raw()["counters"]
    # the pad rows' tokens are routed too, and are no part of the count
    assert counters["engine.rows"] == 3 and counters["engine.pad_rows"] >= 1
    load = want[1]                               # [rows, layers, held]
    assert counters["moe.pairs"] == load.sum()
    assert counters["moe.tokens"] == 3 * 32 * 3
    # the fullest expert of each layer, over the call's rows
    assert counters["moe.busiest_expert_pairs"] == int(
        load.sum(axis=0).max(axis=-1).sum())


def test_an_unmapped_output_is_named_not_dropped():
    """``count_outputs``: a declared counter goes to the metrics, an
    output that is neither a column nor a counter is named in the span's
    attributes."""
    from sparkdl_tpu.graph.function import ModelFunction
    from sparkdl_tpu.transformers.tensor import count_outputs
    from sparkdl_tpu.utils.metrics import Metrics

    mf = ModelFunction(fn=None, output_names=("y", "expert_load", "extra"),
                       counter_names=("expert_load",))
    metrics = Metrics()
    out = {"y": np.ones((2, 3)), "extra": np.zeros((2, 1)),
           "expert_load": np.array([[[1, 2], [0, 4]], [[3, 0], [1, 1]]])}
    kept, attrs = count_outputs(mf, out, metrics, ("y",), tokens=10)
    assert list(kept) == ["y"]
    assert attrs == {"expert_pairs": 12, "unmapped_outputs": "extra"}
    counters = metrics.snapshot_raw()["counters"]
    assert counters["moe.pairs"] == 12 and counters["moe.tokens"] == 20
    assert counters["moe.busiest_expert_pairs"] == 4 + 5
    # a bare array is the one column, as ever
    kept, attrs = count_outputs(mf, np.ones((2, 3)), metrics, ("y",))
    assert list(kept) == ["y"] and attrs == {}


def test_uneven_routing_takes_more_chunks_and_drops_nothing(weights, ids):
    """Tiles of 8 worked off two at a time: a dozen turns of the loop
    where the default runs one — the same sum, to the rounding of its
    order, and every pair computed."""
    f32 = jnp.float32
    variables = program_variables(weights, f32)
    experts = variables["experts"]
    h2 = jax.random.normal(jax.random.PRNGKey(1), (3, 32, 64), f32)
    chosen, weight = et._route(TOY, h2.reshape(-1, 64),
                               experts["mlp.router.gate"][1],
                               experts["mlp.expert_bias"][1])
    # and the worst imbalance: every token to the same four held experts
    for picks in (chosen, jnp.broadcast_to(jnp.asarray([8, 9, 10, 11]),
                                           chosen.shape)):
        run = [et._held_experts(
            TOY, h2, picks, weight, experts["mlp.experts.gate_up_proj"],
            experts["mlp.experts.down_proj"], 8, f32, HIGHEST, **how)
            for how in ({}, {"tile": 8, "chunk_tiles": 2})]
        np.testing.assert_allclose(np.asarray(run[0][0]),
                                   np.asarray(run[1][0]), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(run[0][1]),
                                      np.asarray(run[1][1]))
    assert int(run[1][1].sum()) == 3 * 32 * 4      # all held: none dropped


# -- the tile is read off the shapes ------------------------------------------

def _tile_and_chunk(monkeypatch, routing, rows, positions, width, **how):
    """The tile and the chunk's slots ``_held_experts`` hands the kernel
    at these shapes: traced abstractly, nothing is computed."""
    seen = []

    def record(x, gate_up, down, tile_group, tiles_in_use, first_group=0, *,
               tile, out_dtype, precision):
        seen.append((tile, x.shape[0], tile_group.shape[0]))
        return jnp.zeros((x.shape[0], down.shape[-1]), out_dtype)

    monkeypatch.setattr(gm, "grouped_matmul", record)
    held, k = routing["num_experts"], routing["num_experts_per_tok"]
    bf16, f32 = jnp.bfloat16, jnp.float32
    shape = jax.ShapeDtypeStruct
    jax.eval_shape(
        lambda h2, chosen, weight, gate_up, down: et._held_experts(
            routing, h2, chosen, weight, gate_up, down, 0, bf16, None, **how),
        shape((rows, positions, width), f32),
        shape((rows * positions, k), jnp.int32),
        shape((rows * positions, k), f32),
        shape((held, width, 64), bf16), shape((held, 32, width), bf16))
    (tile, slots, tiles), = seen
    assert slots == tiles * tile
    return tile, slots


#: the three shapes the benchmark dispatches: the keys ``_held_experts``
#: reads, rows x positions, and the tile and the chunk each is given
TRINITY = {"num_experts": 32, "expert_share": [0, 8],
           "num_experts_per_tok": 4}
SDAR = {"num_experts": 128, "expert_share": [0, 1], "num_experts_per_tok": 8}


@pytest.mark.parametrize("routing, rows, positions, tile, slots", [
    (TRINITY, 2, 16384, 256, 96 * 256),        # 512 rows an expert
    (SDAR, 8, 1024, 256, 384 * 256),           # the prefill's group: 512
    # a pass of the loop, 16 rows an expert: a tile an expert expects to
    # fill, a quarter over the pairs' 128 tiles and half a tile an expert
    (SDAR, 64, 4, 16, (160 + 64) * 16),
    # 17 rows an expert: less than its tile, so a WHOLE tile an expert —
    # with every expert held, the worst fall's slots
    (SDAR, 68, 4, 32, 2176 + 128 * 32),
    (SDAR, 1, 4, 16, 32 + 128 * 16),           # a row alone: the floor
], ids=["trinity_large_preview.rows16k", "sdar_30b_a3b_chat.gen256 prefill",
        "sdar_30b_a3b_chat.gen256 loop", "a row over 16 an expert",
        "one row"])
def test_the_tile_is_read_off_the_static_shapes(monkeypatch, routing, rows,
                                                positions, tile, slots):
    assert _tile_and_chunk(monkeypatch, routing, rows, positions,
                           128) == (tile, slots)


def test_an_explicit_tile_wins(monkeypatch):
    assert _tile_and_chunk(monkeypatch, SDAR, 64, 4, 128, tile=8) == (
        8, 2048 + 128 * 8)
    assert _tile_and_chunk(monkeypatch, SDAR, 64, 4, 128, tile=128,
                           chunk_tiles=3) == (128, 3 * 128)


#: 272 tokens, two experts a token of sixteen, the first of them held
#: here (experts 0-7): 34 rows an expert when the routing is even
HALF = {"num_experts": 8, "expert_share": [0, 2], "num_experts_per_tok": 2}
TOKENS = 272


def _first_choice(routing: str, tile: int):
    at = np.arange(TOKENS)
    if routing == "even":
        return at % 8
    if routing == "one expert takes all":
        return np.full(TOKENS, 5)
    # expert 3 one row over a tile, the others what is left
    return np.where(at <= tile, 3, at % 8)


@pytest.mark.parametrize("routing", ["even", "one expert takes all",
                                     "an expert one row over a tile"])
@pytest.mark.parametrize("tile", [gm.MIN_TILE, 32, 64, 256])
def test_held_experts_are_the_dense_sum_at_every_tile(tile, routing):
    """Every pair computed and none dropped, whatever the tile and
    however the pairs fall: against the sum written densely over the
    experts, float32 at ``highest``.  One expert taking all is worked
    off two tiles a turn: as many turns as it takes."""
    f32 = jnp.float32
    d, f = 16, 12
    keys = jax.random.split(jax.random.PRNGKey(tile), 4)
    h = jax.random.normal(keys[0], (2, TOKENS // 2, d), f32)
    gate_up = jax.random.normal(keys[1], (8, d, 2 * f), f32) / 4
    down = jax.random.normal(keys[2], (8, f, d), f32) / 4
    first = _first_choice(routing, tile)
    chosen = jnp.asarray(np.stack([first, 8 + first], axis=1), jnp.int32)
    weight = jax.random.uniform(keys[3], (TOKENS, 2), f32, 0.1, 1.0)
    how = {"chunk_tiles": 2} if routing == "one expert takes all" else {}
    got, load, worked = et._held_experts(
        HALF, h, chosen, weight, gate_up, down, 0, f32, HIGHEST, tile=tile,
        **how)
    tokens = h.reshape(TOKENS, d)
    dense = jnp.zeros((TOKENS, d), f32)
    for e in range(8):
        gate, up = jnp.split(jnp.dot(tokens, gate_up[e], precision=HIGHEST),
                             2, axis=-1)
        out = jnp.dot(jax.nn.silu(gate) * up, down[e], precision=HIGHEST)
        dense = dense + jnp.where(chosen[:, 0] == e, weight[:, 0],
                                  0.0)[:, None] * out
    np.testing.assert_allclose(np.asarray(got).reshape(TOKENS, d),
                               np.asarray(dense), atol=1e-5)
    sizes = np.bincount(first, minlength=8)
    np.testing.assert_array_equal(np.asarray(load).sum(axis=0), sizes)
    # the slots worked through, by hand: whole chunks that cover the
    # tiles in use; two tiles a turn where one expert takes all
    in_use = int(np.ceil(sizes / tile).sum())
    assert int(worked) % tile == 0 and int(worked) >= in_use * tile
    if how:
        assert in_use == -(-TOKENS // tile)
        assert int(worked) == -(-in_use // 2) * 2 * tile
