"""A patch-embedding vision tower: frames of pixels in, the language
model's tokens out, a frame at a time.

The tower is a SigLIP-class ViT (``vision_config`` holds its widths),
followed by a merger of 2x2 neighbouring patches into one token of the
language model's width.  Every ``LN`` is a LayerNorm with a scale and a
bias (eps ``layer_norm_eps``)::

    x    = pixels / 127.5 - 1                        uint8 -> [-1, 1]
    p    = the frame's patches of P x P x 3, raster order, each
           flattened (row, column, channel);  x = p W_pe + b_pe
    for each block:
      h  = LN1(x);  q, k, v = h W_{q,k,v} + b, heads of hd
      q, k turned by the 2-D rotary position: the first half of a head's
           pairs by the patch's row, the second half by its column
           (pairs (i, i + hd/2), theta ``rope_theta``)
      x  = x + softmax(q k^T / sqrt(hd)) v W_o + b_o   over the patches
           of the SAME frame, both ways
      x  = x + GELU_tanh(LN2(x) W_1 + b_1) W_2 + b_2
    x    = LN_post(x)
    m    = LN_pre(x) by patch, then each 2x2 group of neighbouring
           patches joined in the order (0, 0), (0, 1), (1, 0), (1, 1):
           [patches / 4, 4 D_v]
    z    = GELU(m W_m1 + b) W_m2 + b

The blocks run under ONE ``lax.scan``; the frames go through the tower
``chunk`` at a time under a ``lax.map`` (``FRAME_CHUNK``), so the largest
activation is a chunk's ``[chunk x patches, intermediate]``, whatever
the frames of a row.  Attention is ``ops/attention.frame_attention``
with one frame as one row.  Weights and matrix-product operands are in
the compute dtype, accumulated in float32; the residual stream, the
norms, the rotary position and the softmax are float32.  A block's
``q``, ``k`` and ``v`` matrices lie side by side (``self_attn.qkv_proj``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.models.latent_trunk import _layer_norm as layer_norm
from sparkdl_tpu.ops.attention import frame_attention

#: frames that go through the tower together (a chunk's MLP activations
#: [8 x 1024, 4304] bfloat16: 70 MB)
FRAME_CHUNK = 8


def grid(vision: Dict[str, Any]) -> int:
    """Patches along a side of a frame."""
    return vision["image_size"] // vision["patch_size"]


def block_shapes(vision: Dict[str, Any]) -> Dict[str, tuple]:
    """Every weight of one block by its published name, ``[in, out]``."""
    d, f = vision["hidden_size"], vision["intermediate_size"]
    return {"layer_norm1.weight": (d,), "layer_norm1.bias": (d,),
            "self_attn.q_proj.weight": (d, d), "self_attn.q_proj.bias": (d,),
            "self_attn.k_proj.weight": (d, d), "self_attn.k_proj.bias": (d,),
            "self_attn.v_proj.weight": (d, d), "self_attn.v_proj.bias": (d,),
            "self_attn.out_proj.weight": (d, d),
            "self_attn.out_proj.bias": (d,),
            "layer_norm2.weight": (d,), "layer_norm2.bias": (d,),
            "mlp.fc1.weight": (d, f), "mlp.fc1.bias": (f,),
            "mlp.fc2.weight": (f, d), "mlp.fc2.bias": (d,)}


def tower_shapes(vision: Dict[str, Any], out: int) -> Dict[str, tuple]:
    """The weights outside the blocks: patch embedding, the last norm and
    the merger into a width of ``out``."""
    d, p = vision["hidden_size"], vision["patch_size"]
    joined = d * vision["spatial_merge_size"] ** 2
    return {"patch_embedding.weight": (p * p * 3, d),
            "patch_embedding.bias": (d,),
            "post_layernorm.weight": (d,), "post_layernorm.bias": (d,),
            "projector.pre_norm.weight": (d,), "projector.pre_norm.bias": (d,),
            "projector.linear_1.weight": (joined, joined),
            "projector.linear_1.bias": (joined,),
            "projector.linear_2.weight": (joined, out),
            "projector.linear_2.bias": (out,)}


#: a block's weights as the program holds them: q, k, v side by side
QKV = ("self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj")


def stacked_block_shapes(vision: Dict[str, Any]) -> Dict[str, tuple]:
    shapes = {n: s for n, s in block_shapes(vision).items()
              if not n.startswith(QKV)}
    d = vision["hidden_size"]
    shapes["self_attn.qkv_proj.weight"] = (d, 3 * d)
    shapes["self_attn.qkv_proj.bias"] = (3 * d,)
    return shapes


def stack_tower(leaf: Callable[[int, str], Any], vision: Dict[str, Any],
                out: int) -> Dict[str, Any]:
    """The tower's tree from ``leaf(block, published name)`` (``block``
    -1 for the weights outside the blocks), traced: ``blocks`` stacked on
    a leading axis by a ``lax.map`` over the block's index (an int32 of
    the program), so a draw is traced once a name, not once a block."""
    depth = vision["num_hidden_layers"]

    def filled(name):
        suffix = name.rsplit(".", 1)[1]
        pieces = ([f"{p}.{suffix}" for p in QKV]
                  if name.startswith("self_attn.qkv_proj") else [name])
        return lax.map(lambda i: jnp.concatenate([leaf(i, p) for p in pieces],
                                                 axis=-1),
                       jnp.arange(depth, dtype=jnp.int32))

    return {"blocks": {n: filled(n) for n in stacked_block_shapes(vision)},
            **{n: leaf(-1, n) for n in tower_shapes(vision, out)}}


def init(vision: Dict[str, Any], out: int, key, dtype=jnp.bfloat16
         ) -> Dict[str, Any]:
    """Random variables in the tower's tree: matrices N(0, 1/fan-in),
    norm scales 1, biases 0."""
    keys = iter(jax.random.split(key, 64))

    def leaf(shape, name):
        if name.endswith("bias"):
            return jnp.zeros(shape, dtype)
        if len(shape) == 1 or "norm" in name:
            return jnp.ones(shape, dtype)
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / shape[-2] ** 0.5).astype(dtype)

    depth = vision["num_hidden_layers"]
    return {"blocks": {n: leaf((depth,) + s, n)
                       for n, s in stacked_block_shapes(vision).items()},
            **{n: leaf(s, n) for n, s in tower_shapes(vision, out).items()}}


def rotary_angles(vision: Dict[str, Any]):
    """``[patches, hd / 2]`` float32: a patch's row times the first half
    of the inverse frequencies, its column times the second."""
    hd = vision["hidden_size"] // vision["num_attention_heads"]
    side = grid(vision)
    quarter = hd // 4
    inv = float(vision["rope_theta"]) ** (
        -jnp.arange(0, 2 * quarter, 2, dtype=jnp.float32) / (2 * quarter))
    at = jnp.arange(side * side, dtype=jnp.int32)
    row, col = (at // side).astype(jnp.float32), (at % side).astype(jnp.float32)
    return jnp.concatenate([row[:, None] * inv, col[:, None] * inv], axis=-1)


def patches(frames, vision: Dict[str, Any]):
    """uint8 ``[F, S, S, 3]`` -> float32 ``[F, patches, P*P*3]`` in
    [-1, 1], raster order, each flattened (row, column, channel)."""
    f, p, side = frames.shape[0], vision["patch_size"], grid(vision)
    x = frames.astype(jnp.float32) / 127.5 - 1.0
    x = x.reshape(f, side, p, side, p, 3).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(f, side * side, p * p * 3)


def merge(x, vision: Dict[str, Any]):
    """``[F, patches, D]`` -> ``[F, patches / 4, 4 D]``: each 2x2 group of
    neighbouring patches joined."""
    f, _, d = x.shape
    side, m = grid(vision), vision["spatial_merge_size"]
    x = x.reshape(f, side // m, m, side // m, m, d).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(f, (side // m) ** 2, m * m * d)


def apply_frames(variables: Dict[str, Any], frames, vision: Dict[str, Any],
                 *, precision=None):
    """uint8 ``[F, S, S, 3]`` -> the merged tokens ``[F, tokens, out]``
    float32, all frames at once (a chunk of them: ``apply``)."""
    f32 = jnp.float32
    eps = vision["layer_norm_eps"]
    heads = vision["num_attention_heads"]
    d = vision["hidden_size"]
    hd = d // heads
    dtype = variables["patch_embedding.weight"].dtype
    angle = rotary_angles(vision)

    def mm(a, w, b):
        return jnp.dot(a.astype(dtype), w, precision=precision,
                       preferred_element_type=f32) + b.astype(f32)

    with jax.named_scope("patch_embed"):
        x = mm(patches(frames, vision), variables["patch_embedding.weight"],
               variables["patch_embedding.bias"])
    n, p = x.shape[0], x.shape[1]

    def block(x, w):
        with jax.named_scope("vision_attention"):
            h = layer_norm(x, w["layer_norm1.weight"], w["layer_norm1.bias"],
                           eps)
            qkv = mm(h, w["self_attn.qkv_proj.weight"],
                     w["self_attn.qkv_proj.bias"])
            a = frame_attention((qkv[..., :d] * hd ** -0.5).astype(dtype),
                                qkv[..., d:2 * d].astype(dtype),
                                qkv[..., 2 * d:].astype(dtype), angle,
                                heads=heads, precision=precision)
            x = x + mm(a, w["self_attn.out_proj.weight"],
                       w["self_attn.out_proj.bias"])
        with jax.named_scope("vision_mlp"):
            h = layer_norm(x, w["layer_norm2.weight"], w["layer_norm2.bias"],
                           eps)
            u = jax.nn.gelu(mm(h, w["mlp.fc1.weight"], w["mlp.fc1.bias"]),
                            approximate=True).astype(dtype)
            x = x + mm(u, w["mlp.fc2.weight"], w["mlp.fc2.bias"])
        return x, None

    x, _ = lax.scan(block, x, variables["blocks"])
    with jax.named_scope("merger"):
        x = layer_norm(x, variables["post_layernorm.weight"],
                       variables["post_layernorm.bias"], eps)
        m = merge(layer_norm(x, variables["projector.pre_norm.weight"],
                             variables["projector.pre_norm.bias"], eps),
                  vision)
        u = jax.nn.gelu(mm(m, variables["projector.linear_1.weight"],
                           variables["projector.linear_1.bias"]),
                        approximate=False)
        return mm(u, variables["projector.linear_2.weight"],
                  variables["projector.linear_2.bias"])


def apply(variables: Dict[str, Any], frames, vision: Dict[str, Any], *,
          precision=None, chunk: int = FRAME_CHUNK):
    """uint8 ``[F, S, S, 3]`` -> ``[F, tokens, out]`` float32, at most
    ``chunk`` frames at a time (the largest number up to it that divides
    ``F``)."""
    f = frames.shape[0]
    chunk = max(n for n in range(1, min(chunk, f) + 1) if f % n == 0)
    out = lax.map(lambda part: apply_frames(variables, part, vision,
                                            precision=precision),
                  frames.reshape((f // chunk, chunk) + frames.shape[1:]))
    return out.reshape((f,) + out.shape[2:])
