"""Fused separable-conv kernel (ops/sepconv.py) parity and plumbing.

The pallas kernel itself runs here through the PALLAS INTERPRETER
(``force="interpret"``) so CI exercises the real roll/dot/mask kernel
logic on CPU; the compiled-TPU parity was additionally pinned bit-exact
against the same reference on hardware (PERF.md round 4).  Reference
behavior: keras SeparableConv2D + inference BatchNorm
(python/sparkdl/transformers/named_image.py Xception path).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops.sepconv import (flat_width, fused_sepconv_flat,
                                     pad_to_flat, sepconv_reference,
                                     unflatten)

SHAPES = [
    (19, 19, 32, 40),   # middle-flow class (728->728 at full scale)
    (10, 10, 24, 48),   # exit-flow class (post_relu)
    (12, 9, 16, 16),    # non-square, w+2 already a sublane multiple
    (13, 11, 16, 16),   # odd height and width
    (12, 9, 16, 24),    # non-square with c != f
]


def _mats(rng, c, f):
    dwk = jnp.asarray(rng.normal(0, 0.2, (3, 3, c)), jnp.float32)
    pw = jnp.asarray(rng.normal(0, 0.05, (c, f)), jnp.float32)
    scale = jnp.asarray(rng.normal(1, 0.1, (f,)), jnp.float32)
    shift = jnp.asarray(rng.normal(0, 0.1, (f,)), jnp.float32)
    return dwk, pw, scale, shift


def test_flat_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=(2, 7, 5, 3)), jnp.float32)
    xf = pad_to_flat(x, 7, 5)
    assert xf.shape == (2, 9 * flat_width(5), 3)
    np.testing.assert_array_equal(np.asarray(unflatten(xf, 7, 5)),
                                  np.asarray(x))
    # halo positions are zero
    grid = np.asarray(xf).reshape(2, 9, flat_width(5), 3)
    assert np.all(grid[:, 0] == 0) and np.all(grid[:, -1] == 0)
    assert np.all(grid[:, :, 0] == 0) and np.all(grid[:, :, 6:] == 0)


def test_reference_matches_direct_convs(rng):
    """The jax reference twin == explicit depthwise+pointwise+affine."""
    h, w, c, f = 9, 9, 8, 12
    x = jnp.asarray(rng.normal(size=(2, h, w, c)), jnp.float32)
    dwk, pw, scale, shift = _mats(rng, c, f)
    got = sepconv_reference(x, dwk, pw, scale, shift, pre_relu=True,
                            post_relu=True)
    xr = jax.nn.relu(x.astype(jnp.bfloat16))
    dw_out = jax.lax.conv_general_dilated(
        xr, dwk.reshape(3, 3, 1, c).astype(jnp.bfloat16), (1, 1), "SAME",
        feature_group_count=c, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    pw_out = jnp.einsum("nhwc,cf->nhwf", dw_out.astype(jnp.float32),
                        pw.astype(jnp.float32))
    want = jax.nn.relu(pw_out * scale + shift)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.1, atol=0.05)


@pytest.mark.parametrize("h,w,c,f", SHAPES)
@pytest.mark.parametrize("pre_relu,post_relu", [(False, False),
                                                (True, False),
                                                (False, True)])
def test_kernel_parity_interpreted(rng, h, w, c, f, pre_relu, post_relu):
    """The REAL pallas kernel (interpreted) == jax reference, including
    the output-halo contract (zeros, next-layer consumable)."""
    x = jnp.asarray(rng.normal(size=(2, h, w, c)), jnp.float32)
    dwk, pw, scale, shift = _mats(rng, c, f)
    xf = pad_to_flat(x, h, w)
    got_f = fused_sepconv_flat(xf, dwk, pw, scale, shift, h, w,
                               pre_relu, post_relu, force="interpret")
    ref_f = fused_sepconv_flat(xf, dwk, pw, scale, shift, h, w,
                               pre_relu, post_relu, force=False)
    got = np.asarray(unflatten(got_f, h, w), np.float32)
    ref = np.asarray(unflatten(ref_f, h, w), np.float32)
    np.testing.assert_allclose(got, ref, rtol=0.08, atol=0.05)
    # halo contract: kernel output halo is ZERO (chainable)
    wp = flat_width(w)
    grid = np.asarray(got_f, np.float32).reshape(2, h + 2, wp, f)
    assert np.all(grid[:, 0] == 0) and np.all(grid[:, -1] == 0)
    assert np.all(grid[:, :, 0] == 0) and np.all(grid[:, :, w + 1:] == 0)


def test_kernel_chain_interpreted(rng):
    """Two chained kernels with NO repacking == two reference layers —
    the property the Xception middle flow relies on."""
    h, w, c = 13, 13, 16
    x = jnp.asarray(rng.normal(size=(2, h, w, c)), jnp.float32)
    dwk1, pw1, s1, t1 = _mats(rng, c, c)
    dwk2, pw2, s2, t2 = _mats(rng, c, c)
    xf = pad_to_flat(x, h, w)
    a = fused_sepconv_flat(xf, dwk1, pw1, s1, t1, h, w, True, False,
                           force="interpret")
    b = fused_sepconv_flat(a, dwk2, pw2, s2, t2, h, w, True, False,
                           force="interpret")
    got = np.asarray(unflatten(b, h, w), np.float32)
    r1 = sepconv_reference(x, dwk1, pw1, s1, t1, True)
    r2 = sepconv_reference(r1, dwk2, pw2, s2, t2, True)
    np.testing.assert_allclose(got, np.asarray(r2, np.float32),
                               rtol=0.1, atol=0.08)


def test_xception_fused_matches_unfused(rng):
    """Model-level parity: Xception(fused_inference=True) — the pallas
    routing, padded-flat chaining, BNAffine folding — matches the plain
    module graph, from the SAME variables, and both declare identical
    variable trees (weight import/persistence compatibility)."""
    from sparkdl_tpu.models.xception import Xception

    x = jnp.asarray(rng.random((2, 96, 96, 3)) * 2 - 1, jnp.float32)
    m0 = Xception(num_classes=5, fused_inference=False)
    m1 = Xception(num_classes=5, fused_inference=True)
    v0 = m0.init(jax.random.PRNGKey(0), x, train=False)
    v1 = m1.init(jax.random.PRNGKey(0), x, train=False)
    assert (jax.tree_util.tree_structure(v0)
            == jax.tree_util.tree_structure(v1))
    f0 = np.asarray(m0.apply(v0, x, train=False, features=True))
    f1 = np.asarray(m1.apply(v0, x, train=False, features=True))
    np.testing.assert_allclose(f1, f0, rtol=0.05, atol=0.02)
    # train-mode apply takes the unfused branch regardless of the flag
    # (BatchNorm needs batch statistics) and works from fused-init vars
    out, mut = m1.apply(v1, x, train=True, features=True,
                        mutable=["batch_stats"])
    assert out.shape == (2, 2048) and "batch_stats" in mut
