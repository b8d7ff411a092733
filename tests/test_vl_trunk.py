"""The vision-language trunk (``sparkdl_tpu.models.vl_trunk`` over
``sparkdl_tpu.models.vision_tower``) against the benchmark's plain
reference (``benchmark/reference/keye_vl2.py``: float32 at ``highest``,
a frame's attention whole, the indexer's whole score matrix,
``lax.top_k``, each query's softmax over its selected keys) at toy
widths on the CPU, on the reference's seeded weights: the tower and
merger, the positions of the Qwen2-VL rule and the splice, the whole
trunk, and the whole program through ``TFTransformer`` over a uint8 and
an int32 column with its counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import vl_trunk_flops as vf
from benchmark.reference import keye_vl2 as ref
from benchmark.traffic import video_rows
from sparkdl_tpu.models import vision_tower, vl_trunk

#: a tower of 2 blocks (hidden 32, 4 heads of 8, MLP 40) over frames of
#: 8 x 8 in patches of 2 (16 patches, 4 merged tokens a frame); 2
#: language layers (hidden 32, 4 query heads over 2 key/value heads of
#: 16, mRoPE sections 2/4/2), an indexer of 2 heads x 8 keeping the top
#: 4, so that the selection binds from position 4 on; 8 experts of 16,
#: 2 a token; rows of 14 text ids and 3 frames (32 positions)
TOY = {"name": "toy_vl", "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 16,
       "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
       "rms_norm_eps": 1e-6, "rope_theta": 10000000,
       "rope_scaling": {"mrope_section": [2, 4, 2], "rope_type": "default",
                        "type": "default"},
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                     "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                     "q_chunk_size": 512, "topk": 4},
       "vocab_size": 64, "num_hidden_layers": 2, "expert_share": [0, 1],
       "vision_config": {"hidden_size": 32, "intermediate_size": 40,
                         "num_hidden_layers": 2, "num_attention_heads": 4,
                         "patch_size": 2, "image_size": 8,
                         "spatial_merge_size": 2, "layer_norm_eps": 1e-6,
                         "rope_theta": 10000},
       "image_token_id": 61, "vision_start_token_id": 62,
       "vision_end_token_id": 63, "sequence_length": 32, "frames": 3,
       "frame_size": 8}
HIGHEST = jax.lax.Precision.HIGHEST
SEED = 2**31 + 5
TEXT, FRAMES = 14, 3


@pytest.fixture(scope="module")
def weights():
    return ref.draw_weights(TOY, SEED)


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(0)
    ids = np.stack([video_rows.row_ids(rng, TOY, TEXT, FRAMES)
                    for _ in range(3)])
    pixels = rng.integers(0, 256, (3, FRAMES * 8 * 8 * 3), dtype=np.uint8)
    return pixels, ids


@pytest.fixture(scope="module")
def want(weights, rows):
    return ref.forward(TOY, weights, *rows)


def draw(config, parts):
    w = ref.draw_weights(config, parts)
    return {"vision": vision_tower.stack_tower(
                w.vision_leaf, config["vision_config"], config["hidden_size"]),
            "embed_tokens": w.embedding(),
            **vl_trunk.stack_layers(w.leaf, config), "norm": w.final_norm()}


@pytest.fixture(scope="module")
def program_weights():
    """Drawn as the benchmark's kind draws them: ONE program, the seed
    its argument."""
    return jax.jit(lambda parts: draw(TOY, parts))(ref.seed_parts(SEED))


def as_float32(variables):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), variables)


def gap(got, want):
    return float((np.abs(np.asarray(got) - want).max(axis=1)
                  / np.abs(want).max(axis=1)).max())


def test_one_program_draws_every_weight_as_the_reference(weights,
                                                         program_weights):
    """The draw with the seed as an argument holds, bit for bit, what the
    reference draws a weight at a time: q, k, v side by side in a vision
    block, a layer's experts on the leading axis, gate beside up."""
    v = program_weights
    blocks = v["vision"]["blocks"]
    for i in range(TOY["vision_config"]["num_hidden_layers"]):
        np.testing.assert_array_equal(
            blocks["self_attn.qkv_proj.weight"][i], jnp.concatenate([
                weights.vision_leaf(i, f"self_attn.{n}.weight")
                for n in ("q_proj", "k_proj", "v_proj")], axis=-1))
        np.testing.assert_array_equal(blocks["mlp.fc2.bias"][i],
                                      weights.vision_leaf(i, "mlp.fc2.bias"))
    np.testing.assert_array_equal(
        v["vision"]["projector.linear_1.weight"],
        weights.vision_leaf(-1, "projector.linear_1.weight"))
    held = TOY["num_experts"]
    for layer in range(TOY["num_hidden_layers"]):
        np.testing.assert_array_equal(
            v["layers"]["self_attn.indexer.wq"][layer],
            weights.leaf(layer, "self_attn.indexer.wq"))
        np.testing.assert_array_equal(
            v["experts"]["mlp.experts.gate_up_proj"][
                layer * held:(layer + 1) * held],
            jnp.concatenate([weights.leaf(layer, "mlp.experts.gate_proj"),
                             weights.leaf(layer, "mlp.experts.up_proj")], -1))
    np.testing.assert_array_equal(v["embed_tokens"], weights.embedding())


@pytest.mark.parametrize("chunk", [1, 3])
def test_vision_tower_and_merger_are_the_reference(weights, program_weights,
                                                   rows, chunk):
    """Float32 at 'highest', the frames one or three at a time: the
    merged tokens of every frame, in order."""
    pixels, _ = rows
    want = ref.vision_tokens(TOY, weights, pixels)
    frames = jnp.asarray(pixels).reshape(-1, 8, 8, 3)
    got = vision_tower.apply(as_float32(program_weights["vision"]), frames,
                             TOY["vision_config"], precision=HIGHEST,
                             chunk=chunk)
    assert got.shape == (3 * FRAMES, 4, 32)
    got = np.asarray(got).reshape(3, FRAMES * 4, 32)
    for row, w in zip(got, want):
        assert gap(row, w) < 1e-5


def test_the_tower_sees_each_frame_alone(program_weights, rows):
    """A frame's tokens do not move when another frame's pixels do."""
    pixels, _ = rows
    frames = np.asarray(pixels).reshape(-1, 8, 8, 3)
    other = frames.copy()
    other[1] = 255 - other[1]
    run = jax.jit(lambda f: vision_tower.apply(
        as_float32(program_weights["vision"]), f, TOY["vision_config"],
        precision=HIGHEST, chunk=3))
    a, b = np.asarray(run(frames)), np.asarray(run(other))
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[2:], b[2:])
    assert np.abs(a[1] - b[1]).max() > 1e-3


def _layout(*parts):
    """ids from ``("text", n)`` and ``("run", frames)`` parts."""
    out = []
    for kind, n in parts:
        out += (list(range(1, n + 1)) if kind == "text"
                else [61] * (4 * n))
    return np.asarray(out, np.int32)


@pytest.mark.parametrize("parts, expected", [
    # text alone: one stream, three times
    ((("text", 5),), [(i, i, i) for i in range(5)]),
    # a frame of a 2 x 2 grid, then text from the largest + 1
    ((("text", 2), ("run", 1), ("text", 2)),
     [(0, 0, 0), (1, 1, 1), (2, 2, 2), (2, 2, 3), (2, 3, 2), (2, 3, 3),
      (4, 4, 4), (5, 5, 5)]),
    # two frames in one run: t moves a frame, the text after from t's max
    ((("run", 3), ("text", 1)),
     [(0, 0, 0), (0, 0, 1), (0, 1, 0), (0, 1, 1), (1, 0, 0), (1, 0, 1),
      (1, 1, 0), (1, 1, 1), (2, 0, 0), (2, 0, 1), (2, 1, 0), (2, 1, 1),
      (3, 3, 3)]),
    # two runs apart
    ((("run", 1), ("text", 1), ("run", 1)),
     [(0, 0, 0), (0, 0, 1), (0, 1, 0), (0, 1, 1), (2, 2, 2), (3, 3, 3),
      (3, 3, 4), (3, 4, 3), (3, 4, 4)]),
])
def test_mrope_positions_are_the_qwen2_vl_rule(parts, expected):
    """The program's positions, worked out from the ids on the device,
    against the reference's position-at-a-time rule and by hand."""
    ids = _layout(*parts)
    got = np.asarray(vl_trunk.mrope_positions(jnp.asarray(ids), 61, 2))
    np.testing.assert_array_equal(got, ref.positions(TOY, ids))
    np.testing.assert_array_equal(got, np.asarray(expected))


def test_the_nth_placeholder_takes_the_nth_token():
    ids = jnp.asarray(_layout(("text", 2), ("run", 1), ("text", 1),
                              ("run", 1)))
    embedded = jnp.zeros((len(ids), 2))
    tokens = jnp.arange(16.0).reshape(8, 2) + 1
    x, count = vl_trunk.splice(embedded, ids, tokens, 61)
    assert int(count) == 8
    placed = np.asarray(x)[np.asarray(ids) == 61]
    np.testing.assert_array_equal(placed, np.asarray(tokens))
    assert not np.asarray(x)[np.asarray(ids) != 61].any()


@pytest.mark.parametrize("query_chunk", [32, 8])
def test_float32_trunk_is_the_reference(program_weights, rows, want,
                                        query_chunk, tied_by_hand):
    """Float32 at 'highest' over the same numbers, the queries in one
    chunk or four: the order of the sums alone differs.  The queries
    whose selection took the tie passes are those counted by hand."""
    got = vl_trunk.apply(as_float32(program_weights), *rows, TOY,
                         precision=HIGHEST, query_chunk=query_chunk)
    assert got["features"].shape == (3, 32)
    assert gap(got["features"], want) < 1e-5
    t = TOY["sequence_length"]
    counts = np.asarray(got["sparse_counts"])
    assert counts.shape == (3, 2, 4)
    np.testing.assert_array_equal(
        counts[0, 0, :3], [t * (t + 1) // 2, 1 + 2 + 3 + 4 * (t - 3), t * t])
    assert counts[..., 3].sum() == tied_by_hand()
    np.testing.assert_array_equal(np.asarray(got["vision_counts"]),
                                  [[FRAMES, FRAMES * 16, FRAMES * 4]] * 3)


def test_the_program_through_tftransformer(program_weights, rows, want):
    """The whole program on the normal path: a uint8 list column of
    frames and an int32 list column of ids into ONE program on the
    cached engine; the counters reach the engine's metrics and no pixel
    is counted as a token."""
    import pyarrow as pa

    from sparkdl_tpu import TFTransformer
    from sparkdl_tpu.frame import DataFrame

    pixels, ids = rows

    def column(values):
        offsets = pa.array(np.arange(len(values) + 1, dtype=np.int32)
                           * values.shape[1])
        return pa.ListArray.from_arrays(offsets, pa.array(values.reshape(-1)))

    frame = DataFrame(pa.table({"clip": column(pixels), "tokens": column(ids)}))
    stage = TFTransformer(
        modelFunction=vl_trunk.model_function(
            TOY, as_float32(program_weights), matmul_precision="highest"),
        inputMapping={"clip": "pixels", "tokens": "ids"},
        outputMapping={"features": "features"}, batchSize=2)
    out = stage.transform(frame)
    assert gap(out.column_to_numpy("features"), want) < 1e-5
    counters = stage.engine().metrics.snapshot_raw()["counters"]
    assert counters["vision.frames"] == 3 * FRAMES
    assert counters["vision.patches"] == 3 * FRAMES * 16
    assert counters["vision.spliced_tokens"] == 3 * FRAMES * 4
    assert counters["sparse.selected_pairs"] == 3 * 2 * 122
    assert 0 <= counters["sparse.tied_queries"] <= 3 * 2 * 32
    assert "moe.tokens" not in counters


def test_flops_by_hand_at_toy_size():
    """``vl_trunk_flops`` at the toy's widths, counted by hand."""
    p, d, f, t = 16, 32, 40, 32
    frame = (2 * p * 12 * d + 2 * (2 * p * (4 * d * d + 2 * d * f))
             + 2 * 4 * p * p * d + 2 * 4 * (4 * d * 4 * d + 4 * d * 32))
    assert vf.frame_flops(TOY) == frame
    token = 2 * (32 * 64 + 2 * 32 * 32 + 64 * 32 + 32 * 16 + 32 * 8
                 + 32 * 2 + 32 * 8) + 2 * 2 * 3 * 32 * 16
    pairs = t * (t + 1) // 2 * 2 * 2 * 9 + 122 * 4 * 4 * 16
    assert vf.flops_per_row(TOY) == FRAMES * frame + 2 * (t * token + pairs)
