"""Generation by diffusion over blocks (``sparkdl_tpu.models.
block_diffusion``) against the benchmark's plain reference
(``benchmark/reference/sdar.py``: float32 at ``highest``, two streams,
the mask written out, no cache) at toy widths on the CPU, on the
reference's seeded weights: the sampler's trajectory, what its passes
read, the cache, a row's place in the batch, the stage and its
counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar as ref
from sparkdl_tpu.models import block_diffusion as bd
from sparkdl_tpu.models import expert_trunk as et

#: hidden 64; two layers of 8 experts of width 32, 2 a token; 4 query / 2
#: key-value heads of 16; a vocabulary of 97 with the mask id inside it;
#: prompts of 16 ids, 16 generated in blocks of 4
TOY = {"name": "toy_diffusion", "hidden_size": 64, "head_dim": 16,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "moe_intermediate_size": 32, "num_experts": 8,
       "num_experts_per_tok": 2, "norm_topk_prob": True,
       "num_hidden_layers": 2, "vocab_size": 97, "rope_theta": 1000000,
       "rms_norm_eps": 1e-6, "block_length": 4, "mask_token_id": 90,
       "prompt_length": 16, "generated_length": 16, "denoise_steps": 2}
#: seeds 0-7 were run at 1, 2 and 4 passes a block through the program
#: and through the sampler below: none of them has a choice, a reveal or
#: a routing within rounding of a tie (float32 at 'highest' on both
#: sides: every id and every pass the same); 3 is one of them
SEED = 3
HIGHEST = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module")
def weights():
    return ref.draw_weights(TOY, SEED)


@pytest.fixture(scope="module")
def prompts():
    ids = np.random.default_rng(SEED).integers(0, 96, (3, 16), dtype=np.int32)
    return ids + (ids >= TOY["mask_token_id"])


def program_variables(weights, dtype=jnp.float32, config=TOY):
    def cast(leaf):
        return jnp.asarray(leaf).astype(dtype)

    return {"embed_tokens": cast(weights.embedding()),
            **bd.stack_layers(lambda i, name: cast(weights.leaf(i, name)),
                              config),
            "norm": cast(weights.final_norm()),
            "lm_head": cast(weights.lm_head())}


def generate(weights, prompts, steps=2, config=TOY):
    out = jax.jit(lambda v, ids: bd.apply(
        v, ids, config, generated_length=config["generated_length"],
        denoise_steps=steps, precision=HIGHEST))(
            program_variables(weights, config=config), prompts)
    return {name: np.asarray(a) for name, a in out.items()}


def plain_sampler(config, weights, prompts):
    """The family's sampler written over the reference's two-stream pass:
    block after block, pass after pass, ONE whole forward pass each."""
    c = config
    b, mask = c["block_length"], c["mask_token_id"]
    rows, length = len(prompts), c["generated_length"]
    generated = np.zeros((rows, length), np.int32)
    revealed_at = np.zeros((rows, length), np.int32)
    for first in range(0, length, b):
        for step in range(1, c["denoise_steps"] + 1):
            noisy = np.where(revealed_at > 0, generated, mask).astype(np.int32)
            top, lse, ids = ref.forward(
                c, weights, np.concatenate([prompts, noisy], axis=1), noisy)
            block = slice(first, first + b)
            confidence = np.where(revealed_at[:, block] == 0,
                                  (top - lse)[:, block], -np.inf)
            order = np.argsort(-confidence, axis=1, kind="stable")
            for row in range(rows):
                for at in first + order[row, :b // c["denoise_steps"]]:
                    generated[row, at] = ids[row, at]
                    revealed_at[row, at] = step
    return generated, revealed_at


def gap(got, want):
    return float((np.abs(np.asarray(got) - want).max(axis=1)
                  / np.abs(want).max(axis=1)).max())


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_the_program_is_the_plain_sampler_and_reads_what_it_reads(
        weights, prompts, steps):
    config = {**TOY, "denoise_steps": steps}
    got = generate(weights, prompts, steps)
    generated, revealed_at = plain_sampler(config, weights, prompts)
    np.testing.assert_array_equal(got["generated"], generated)
    np.testing.assert_array_equal(got["revealed_at"], revealed_at)
    assert got["generated"].dtype == got["revealed_at"].dtype == np.int32
    assert (got["generated"] != TOY["mask_token_id"]).all()
    # every position is revealed once, block_length / steps of a block a pass
    per_pass = got["revealed_at"].reshape(3, 4, 4)
    for step in range(1, steps + 1):
        assert ((per_pass == step).sum(axis=-1) == 4 // steps).all()
    # the prefill and the passes through the CACHE read what the whole
    # two-stream pass reads: the chosen logit, the logsumexp, and nothing
    # short of the best confidence left masked
    want = ref.replay(config, weights, prompts, generated, revealed_at)
    assert got["features"].shape == (3, 48)
    assert gap(got["features"], want) < 1e-5
    assert (want.reshape(3, 16, 3)[..., 2] == 0).all()
    counts = got["diffusion_counts"]
    np.testing.assert_array_equal(counts[:, 0], 4 * steps)    # denoise passes
    np.testing.assert_array_equal(counts[:, 1], 4)            # commit passes
    np.testing.assert_array_equal(counts[:, 2], 16)           # ids revealed
    positions = 16 + 4 * (steps + 1) * 4
    np.testing.assert_array_equal(counts[:, 3], 2 * positions)
    np.testing.assert_array_equal(counts[:, 4], 2 * 2 * positions)
    # a dispatch's touched experts on its first row: at most all 8 a layer
    assert 0 < counts[0, 5] <= 2 * 8 * 4 * (steps + 1)
    # and the slots its chunk loop worked through, by hand: a pass routes
    # 3 rows x 4 positions x 2 = 24 pairs to 8 experts, 3 an expert, so
    # the tile is the smallest (16) and the chunk a whole tile an expert
    # over the pairs' two: the worst fall's 10 tiles, ONE turn a layer
    assert counts.shape == (3, len(bd.COUNTS)) == (3, 8)
    assert counts[0, 6] == 10 * 16 * 2 * 4 * (steps + 1)
    # the cache's positions its attention fetched: off the TPU the
    # ``jax.numpy`` form reads a layer's whole cache (16 + 16 positions)
    # at every pass of every layer
    assert bd.COUNTS[7] == "cache_positions"
    assert counts[0, 7] == 4 * (steps + 1) * 2 * (16 + 16)
    assert (counts[1:, 5:] == 0).all()


def test_the_cache_holds_the_commit_passes_keys(weights, prompts,
                                                monkeypatch):
    want = generate(weights, prompts)
    # the last denoise pass's keys (half of them from mask ids) instead
    monkeypatch.setattr(bd, "_turn", lambda step, steps: (
        step < steps, step == steps - 1))
    got = generate(weights, prompts)
    reference = ref.replay(TOY, weights, prompts, got["generated"],
                           got["revealed_at"])
    assert gap(want["features"], ref.replay(
        TOY, weights, prompts, want["generated"], want["revealed_at"])) < 1e-5
    assert gap(got["features"], reference) > 1e-3


def test_a_rows_ids_do_not_depend_on_its_place_in_the_batch(weights, prompts):
    whole = generate(weights, prompts)
    order = [2, 0, 1]
    permuted = generate(weights, prompts[order])
    alone = generate(weights, prompts[1:2])
    for name in ("generated", "revealed_at", "features"):
        np.testing.assert_array_equal(permuted[name], whole[name][order])
        np.testing.assert_array_equal(alone[name], whole[name][1:2])


def test_the_softmax_router_over_all_experts_is_the_dense_sum():
    """``expert_share [0, 1]``: every expert is held, every pair is
    computed, and the dispatch and combine give the sum written densely
    over all experts."""
    f32 = jnp.float32
    routing = {"num_experts": 8, "expert_share": [0, 1],
               "num_experts_per_tok": 3, "route_norm": True,
               "route_scale": 1.0}
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    h = jax.random.normal(keys[0], (2, 5, 16), f32)
    router = jax.random.normal(keys[1], (16, 8), f32)
    gate_up = jax.random.normal(keys[2], (8, 16, 24), f32) / 4
    down = jax.random.normal(keys[3], (8, 12, 16), f32) / 4
    chosen, weight = et._route(routing, h.reshape(10, 16), router,
                               scores="softmax")
    p = jax.nn.softmax(jnp.dot(h.reshape(10, 16), router, precision=HIGHEST))
    top = jnp.sort(p, axis=-1)[:, -3:]
    np.testing.assert_allclose(np.sort(np.asarray(weight), axis=-1),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-6)
    got, load, _ = et._held_experts(routing, h, chosen, weight, gate_up, down,
                                    0, f32, HIGHEST, tile=8)
    dense = jnp.zeros((10, 16), f32)
    for e in range(8):
        gate, up = jnp.split(jnp.dot(h.reshape(10, 16), gate_up[e],
                                     precision=HIGHEST), 2, axis=-1)
        out = jnp.dot(jax.nn.silu(gate) * up, down[e], precision=HIGHEST)
        share = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        dense = dense + share[:, None] * out
    np.testing.assert_allclose(np.asarray(got).reshape(10, 16),
                               np.asarray(dense), rtol=1e-5, atol=1e-6)
    assert int(load.sum()) == 10 * 3 and load.shape == (2, 8)


def test_the_stage_writes_integer_columns_and_counts_its_passes(weights,
                                                                prompts):
    import pyarrow as pa

    from sparkdl_tpu import TFTransformer, obs
    from sparkdl_tpu.frame import DataFrame

    mf = bd.model_function(TOY, program_variables(weights),
                           generated_length=16, denoise_steps=2,
                           matmul_precision="highest")
    stage = TFTransformer(
        modelFunction=mf, inputMapping={"prompt": "ids"},
        outputMapping={"generated": "generated", "revealed_at": "passes",
                       "features": "features"}, batchSize=2)
    df = DataFrame(pa.table({"prompt": pa.array(
        prompts.tolist(), type=pa.list_(pa.int32()))}))
    tracer = obs.configure(enabled=True)
    try:
        out = stage.transform(df)
        spans = {name: [s for s in tracer.snapshot() if s["name"] == name]
                 for name in ("transform.run", "transform.pack_in",
                              "transform.pack_out")}
    finally:
        obs.configure_from_env()
    want = generate(weights, prompts)
    int_list = pa.list_(pa.int32())
    assert out.table.column("generated").type == int_list
    assert out.table.column("passes").type == int_list
    assert out.table.column("features").type == pa.list_(pa.float32())
    np.testing.assert_array_equal(out.column_to_numpy("generated"),
                                  want["generated"])
    np.testing.assert_array_equal(out.column_to_numpy("passes"),
                                  want["revealed_at"])
    np.testing.assert_array_equal(out.column_to_numpy("features"),
                                  want["features"])
    # two dispatches (2 + 1 rows): the counter's sums over the real rows
    counters = stage.engine().metrics.snapshot_raw()["counters"]
    assert counters["diffusion.denoise_passes"] == 3 * 8
    assert counters["diffusion.commit_passes"] == 3 * 4
    assert counters["diffusion.revealed_ids"] == 3 * 16
    assert counters["moe.tokens"] == 3 * 2 * (16 + 48)
    assert counters["moe.pairs"] == 2 * counters["moe.tokens"]
    assert counters["diffusion.touched_experts"] > 0
    # the engine rounds the dispatch up to the mesh's 8 rows (3 real):
    # 64 pairs a pass in the worst fall's 12 tiles of 16, one turn a
    # layer, 12 passes x 2 layers; the slots' fill is the loop's pairs
    # (the padded rows' too: they are laid out like any) over the slots
    assert counters["diffusion.expert_slots"] == 12 * 2 * 12 * 16
    assert 12 * 2 * 64 / counters["diffusion.expert_slots"] == 1 / 3
    # that dispatch's 12 passes x 2 layers over the whole cache of 32
    # (the ``jax.numpy`` path); the filled lengths' sum over it is the
    # share of the fetched positions that were needed
    assert counters["diffusion.cache_positions"] == 12 * 2 * 32
    filled = 2 * 3 * sum(16 + 4 * block for block in range(4))
    assert filled / counters["diffusion.cache_positions"] == 0.6875
    assert counters["engine.rows"] == 3
    (run,), (pack_in,) = spans["transform.run"], spans["transform.pack_in"]
    assert run["attrs"]["prompt_tokens"] == 3 * 16
    assert run["attrs"]["generated_ids"] == 3 * 16
    assert run["attrs"]["denoise_passes"] == 3 * 8
    assert run["attrs"]["commit_passes"] == 3 * 4
    assert run["attrs"]["expert_slots"] == counters["diffusion.expert_slots"]
    assert "unmapped_outputs" not in run["attrs"]
    assert pack_in["attrs"] == {"rows": 3, "bytes": 3 * 16 * 4}
    assert [(s["attrs"]["column"], s["attrs"]["dtype"])
            for s in spans["transform.pack_out"]] == [
        ("generated", "int32"), ("passes", "int32"),
        ("features", "float32")]


def test_tf_transformer_keeps_integer_inputs_integers():
    import pyarrow as pa

    from sparkdl_tpu import TFTransformer
    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction

    table = jnp.arange(50, dtype=jnp.float32).reshape(10, 5)
    mf = ModelFunction(
        fn=lambda v, d: {"row": jnp.take(v, d["ids"][:, 0], axis=0),
                         "twice": 2 * d["ids"]},
        variables=table, input_names=("ids",), output_names=("row", "twice"))
    df = DataFrame(pa.table({"ids": pa.array(
        [[3, 1], [9, 0], [0, 2]], type=pa.list_(pa.int32()))}))
    out = TFTransformer(modelFunction=mf, inputMapping={"ids": "ids"},
                        outputMapping={"row": "row", "twice": "twice"},
                        batchSize=2).transform(df)
    np.testing.assert_array_equal(out.column_to_numpy("row"),
                                  np.asarray(table)[[3, 9, 0]])
    assert out.table.column("twice").type == pa.list_(pa.int32())
    np.testing.assert_array_equal(out.column_to_numpy("twice"),
                                  [[6, 2], [18, 0], [0, 4]])
