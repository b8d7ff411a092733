"""Where the program's vision tower and selection part from the
reference's: ``python3 -m benchmark.tests.vl_agreement --seed <n>``
(on the chip; on a CPU a toy size with ``--toy``).

Over the first distinct row of ``keye_vl_2_30b_a3b.video32k`` (the
generator's own draw for the seed):

* the merged tokens: the program's tower (``vision_tower.apply``, its
  bfloat16 weights as the kind draws them, the frame-attention kernel on
  the chip) against the reference's float32 tower at ``highest``; the
  worst and the median per-token gap ``max|got - want| / max|want|``,
  which the pooled feature alone could hide;
* the selection of layer 0: both sides are given the SAME input (the
  reference's merged tokens spliced into the embedding), so what parts
  them is the indexer's arithmetic (bfloat16 operands in the kernel,
  float32 at ``highest`` in the reference) and nothing carried in; the
  share of the selected pairs that one side selected and the other did
  not, and the queries with any.

One JSON line."""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--toy", action="store_true")
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmark import harness
    from benchmark.reference import keye_vl2 as ref
    from benchmark.traffic import video_rows
    from sparkdl_tpu.models import (expert_trunk, latent_trunk, vision_tower,
                                    vl_trunk)
    from sparkdl_tpu.ops import sparse_attention as sa

    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "keye_vl_2_30b_a3b.json")) as fh:
        c = json.load(fh)
    with open(os.path.join(harness.BENCH_DIR, "traffic",
                           "video_rows_32k.json")) as fh:
        mix = json.load(fh)
    if args.toy:
        from benchmark.tests.test_vl_trunk import MIX, TOY
        c, mix = TOY, MIX
    t, size = c["sequence_length"], c["frame_size"]
    rng = np.random.default_rng([args.seed, 6])
    ids = np.stack([video_rows.row_ids(rng, c, mix["text_ids"],
                                       mix["clip_frames"])
                    for _ in range(mix["distinct_rows"])])[0]
    pixels = rng.integers(0, 256, (mix["distinct_rows"],
                                   c["frames"] * size * size * 3),
                          dtype=np.uint8)[:1]
    weights = ref.draw_weights(c, args.seed)
    f32, bf16 = jnp.float32, jnp.bfloat16
    vision = c["vision_config"]

    # -- the merged tokens
    tower = jax.jit(lambda parts: vision_tower.stack_tower(
        ref.draw_weights(c, parts).vision_leaf, vision, c["hidden_size"]))(
            ref.seed_parts(args.seed))
    got_z = np.asarray(jax.jit(lambda w, f: vision_tower.apply(
        w, f, vision))(tower, jnp.asarray(pixels.reshape(-1, size, size, 3))))
    got_z = got_z.reshape(-1, c["hidden_size"])
    del tower
    want_z = ref.vision_tokens(c, weights, pixels)[0]
    token_gap = (np.abs(got_z - want_z).max(axis=1)
                 / np.maximum(np.abs(want_z).max(axis=1), 1e-30))

    # -- the selection of layer 0, over the same input on both sides
    x = ref.embed_row(c, weights, ids, want_z)
    w = {n: weights.leaf(0, n) for n in ref.layer_shapes(c)
         if n.startswith("self_attn.indexer.") or n == "input_layernorm"}
    sc, eps = c["sa_config"], c["rms_norm_eps"]
    ih, idim, top = (sc["indexer_num_heads"], sc["indexer_head_dim"],
                     sc["topk"])
    sections = [s // 2 for s in c["rope_scaling"]["mrope_section"]]
    pos = ref.positions(c, ids)
    chunk = min(vl_trunk.QUERY_CHUNK, t)

    @jax.jit
    def program(x, w, pos):
        h = expert_trunk._rms_norm(x, w["input_layernorm"], eps).astype(bf16)
        angle = vl_trunk.mrope_angles(pos, idim, sections, c["rope_theta"])
        k_i = latent_trunk._turn(latent_trunk._layer_norm(
            jnp.dot(h, w["self_attn.indexer.wk"], preferred_element_type=f32),
            w["self_attn.indexer.k_norm.weight"],
            w["self_attn.indexer.k_norm.bias"], eps), angle,
            False).astype(bf16)

        def one(first):
            at = first + jnp.arange(chunk, dtype=jnp.int32)
            h_c = lax.dynamic_slice_in_dim(h, first, chunk)
            q_i = latent_trunk._turn(jnp.dot(
                h_c, w["self_attn.indexer.wq"],
                preferred_element_type=f32).reshape(chunk, ih, idim),
                lax.dynamic_slice_in_dim(angle, first, chunk), False)
            weight = jnp.dot(h_c, w["self_attn.indexer.weights_proj"],
                             preferred_element_type=f32) * (ih * idim) ** -0.5
            scores = sa.indexer_scores(q_i.astype(bf16), k_i, weight, first)
            sel = sa.topk_select(scores, first, top)
            return sa.selected(scores, sel, at[:, None],
                               jnp.arange(t, dtype=jnp.int32)[None, :])

        return lax.map(one, jnp.arange(0, t, chunk, dtype=jnp.int32))

    got = np.asarray(program(x, w, jnp.asarray(pos))).reshape(t, t)
    wf = {n: v.astype(f32) for n, v in w.items()}
    block = min(256, t)

    @jax.jit
    def reference(x, wf):
        with jax.default_matmul_precision("highest"):
            h = ref.rms_norm(x, wf["input_layernorm"], eps)
            angle = ref.mrope_angles(pos, idim, sections,
                                     float(c["rope_theta"]))
            k_i = ref.rotate(ref.layer_norm(
                h @ wf["self_attn.indexer.wk"],
                wf["self_attn.indexer.k_norm.weight"],
                wf["self_attn.indexer.k_norm.bias"], eps), angle)
            q_i = ref.rotate((h @ wf["self_attn.indexer.wq"]).reshape(
                t, ih, idim), angle)
            weight = (h @ wf["self_attn.indexer.weights_proj"]) / np.sqrt(
                ih * idim)

            def one(first):
                query_at = first + jnp.arange(block)
                index = jnp.einsum(
                    "qj,qjk->qk", lax.dynamic_slice_in_dim(weight, first, block),
                    jax.nn.relu(jnp.einsum(
                        "qjd,kd->qjk",
                        lax.dynamic_slice_in_dim(q_i, first, block), k_i)))
                causal = jnp.arange(t)[None, :] <= query_at[:, None]
                _, chosen = lax.top_k(jnp.where(causal, index, -jnp.inf), top)
                return jnp.zeros((block, t), bool).at[
                    jnp.arange(block)[:, None], chosen].set(True) & causal

            return lax.map(one, jnp.arange(0, t, block))

    want = np.asarray(reference(x, wf)).reshape(t, t)
    differ = np.logical_xor(got, want)
    line = {"seed": args.seed, "positions": t,
            "merged_tokens": int(len(token_gap)),
            "worst_token_gap": float(token_gap.max()),
            "median_token_gap": float(np.median(token_gap)),
            "selected_pairs": int(want.sum()),
            "program_selected_pairs": int(got.sum()),
            "pairs_one_side_only": int(differ.sum()),
            "share_of_selected_keys_differing": float(
                differ.sum() / 2 / want.sum()),
            "queries_with_any": int(differ.any(axis=1).sum()),
            "device": jax.devices()[0].platform}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
