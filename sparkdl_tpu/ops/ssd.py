"""The selective state-space scan of a Mamba-2 mixer, in its chunked
form (state-space duality): a Pallas kernel on the TPU, the same
algebra in ``jax.numpy`` elsewhere.

By head, with a state ``S`` of ``[P, N]`` (head size x state size)::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D * x_t

``B`` and ``C`` are shared by the heads of a group.  Written token by
token (``ssd_recurrence``, the tests' oracle and nothing else) this is
``T`` dependent steps of a few hundred operations each.  The chunked
form cuts the sequence into chunks of ``Q`` positions; with
``cum_t = sum_{s<=t} dt_s A`` inside a chunk,

* within a chunk ``y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s)
  dt_s x_s`` — a masked, decayed ``(C B^T) x``: two matrix products;
* from the chunks before, ``y_t += exp(cum_t) * S C_t`` with the state
  ``S`` the chunk was entered with;
* the state leaves the chunk as ``exp(cum_Q) S + sum_s exp(cum_Q -
  cum_s) dt_s x_s (outer) B_s`` and is carried to the next.

So the work is matrix products of chunk size and one ``[P, N]`` float32
state a head that crosses the chunks in order.  The kernel
(``name="ssd_scan"``) runs a grid rows x groups x chunks, the chunk axis
sequential, the states of a group's heads in VMEM scratch; ``C B^T`` of
a chunk is computed once a group and used by all its heads.  Matrix
products take their operands in ``x``'s dtype and accumulate in
float32; the decays, their running sums and the carried state are
float32.  The running sums of ``dt * A`` by chunk are formed outside
the kernel (``[rows, T, heads]`` float32, 1/128 of ``x``'s size).

``ssd_scan`` picks the kernel by the platform, as ``ops/sepconv`` does,
and by nothing else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "ssd_scan"


def _on_tpu() -> bool:
    """True iff the process's default backend is the TPU (a backend
    that cannot initialise raises, as in ``ops/sepconv``)."""
    return jax.default_backend() == "tpu"


def ssd_recurrence(x, dt, a, b, c, d):
    """The recurrence as it is written, one position after the other, in
    float32: the oracle of the tests.  ``x`` ``[R, T, H, P]``, ``dt``
    ``[R, T, H]``, ``a`` and ``d`` ``[H]``, ``b`` and ``c``
    ``[R, T, G, N]``; returns ``[R, T, H, P]`` float32."""
    f32 = jnp.float32
    r, t, h, p = x.shape
    g, n = b.shape[2:]
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    b = jnp.repeat(b, h // g, axis=2)                       # [R, T, H, N]
    c = jnp.repeat(c, h // g, axis=2)

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        decay = jnp.exp(dt_t * a.astype(f32))               # [R, H]
        state = (state * decay[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        y_t = (state * c_t[..., None, :]).sum(-1) + d.astype(f32)[:, None] * x_t
        return state, y_t

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    _, y = lax.scan(step, jnp.zeros((r, h, p, n), f32), xs)
    return jnp.moveaxis(y, 0, 1)


def _chunk_sums(dt, a, chunk: int):
    """``cum_t = sum_{s<=t} dt_s A`` within each chunk, float32
    ``[R, T, H]``."""
    r, t, h = dt.shape
    adt = dt.astype(jnp.float32) * a.astype(jnp.float32)
    return jnp.cumsum(adt.reshape(r, t // chunk, chunk, h),
                      axis=2).reshape(r, t, h)


def ssd_chunked(x, dt, a, b, c, d, chunk: int, precision=None):
    """The chunked form in ``jax.numpy``: a ``lax.scan`` over the chunks
    that carries the states of all heads.  Shapes as ``ssd_recurrence``;
    returns ``x``'s dtype."""
    f32 = jnp.float32
    r, t, h, p = x.shape
    g, n = b.shape[2:]
    if t % chunk:
        raise ValueError(f"{t} positions are no multiple of the chunk {chunk}")
    nc, hg = t // chunk, h // g
    cum = _chunk_sums(dt, a, chunk)

    def by_chunk(v):                     # [R, T, ...] -> [nc, R, Q, ...]
        return jnp.moveaxis(v.reshape((r, nc, chunk) + v.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    dot = functools.partial(jnp.einsum, precision=precision,
                            preferred_element_type=f32)

    def step(state, at):
        x_c, dt_c, cum_c, b_c, c_c = at
        xg = x_c.reshape(r, chunk, g, hg, p)
        dtg = dt_c.astype(f32).reshape(r, chunk, g, hg)
        cumg = cum_c.reshape(r, chunk, g, hg)
        cb = dot("rtgn,rsgn->rgts", c_c, b_c)               # [R, G, Q, Q]
        diff = cumg[:, :, None] - cumg[:, None]             # [R, t, s, G, hg]
        decay = jnp.where(lower[None, :, :, None, None],
                          jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        m = (jnp.moveaxis(cb, 1, 3)[..., None] * decay
             * dtg[:, None])                                # [R, t, s, G, hg]
        y = dot("rtsgh,rsghp->rtghp", m.astype(x.dtype), xg)
        y += jnp.exp(cumg)[..., None] * dot(
            "rtgn,rghpn->rtghp", c_c, state.astype(x.dtype))
        last = cumg[:, -1]                                  # [R, G, hg]
        w = dtg * jnp.exp(last[:, None] - cumg)             # [R, Q, G, hg]
        new = dot("rsghp,rsgn->rghpn", (xg.astype(f32)
                                        * w[..., None]).astype(x.dtype), b_c)
        state = jnp.exp(last)[..., None, None] * state + new
        return state, y.reshape(r, chunk, h, p)

    xs = tuple(by_chunk(v) for v in (x, dt, cum, b, c))
    _, y = lax.scan(step, jnp.zeros((r, g, hg, p, n), f32), xs)
    y = jnp.moveaxis(y, 0, 1).reshape(r, t, h, p)
    y += d.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype)


def _ssd_kernel(x_ref, b_ref, c_ref, dtc_ref, cumc_ref, dtr_ref, cumr_ref,
                d_ref, y_ref, state_ref, *, heads: int, p: int, precision):
    """One chunk of one group of one row: ``heads`` heads of size ``p``
    side by side in ``x_ref`` ``[1, Q, heads*p]``; ``b_ref``/``c_ref``
    ``[1, Q, N]``; ``dt`` and ``cum`` as columns ``[1, 1, Q, heads]`` and
    as rows ``[1, 1, heads, Q]``; ``d_ref`` ``[1, 1, heads*p]``;
    ``state_ref`` ``[heads, p, N]`` float32, kept across the chunks."""
    f32 = jnp.float32
    q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    bm, cm = b_ref[0], c_ref[0]                             # [Q, N]
    dims_nt = (((1,), (1,)), ((), ()))
    cb = lax.dot_general(cm, bm, dims_nt, precision=precision,
                         preferred_element_type=f32)        # [Q, Q]
    rows = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = rows >= cols
    at_end = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    for j in range(heads):
        xh = x_ref[0, :, j * p:(j + 1) * p]                 # [Q, P]
        dt_c = dtc_ref[0, 0, :, j:j + 1]                    # [Q, 1]
        cum_c = cumc_ref[0, 0, :, j:j + 1]
        dt_r = dtr_ref[0, 0, j:j + 1, :]                    # [1, Q]
        cum_r = cumr_ref[0, 0, j:j + 1, :]
        decay = jnp.where(lower, jnp.exp(jnp.minimum(cum_c - cum_r, 0.0)),
                          0.0)
        m = (cb * decay * dt_r).astype(xh.dtype)
        y = jnp.dot(m, xh, precision=precision, preferred_element_type=f32)
        state = state_ref[j]                                # [P, N]
        y += jnp.exp(cum_c) * lax.dot_general(
            cm, state.astype(xh.dtype), dims_nt, precision=precision,
            preferred_element_type=f32)
        y += d_ref[0, :, j * p:(j + 1) * p] * xh.astype(f32)
        y_ref[0, :, j * p:(j + 1) * p] = y.astype(y_ref.dtype)
        # the chunk's last running sum, as a scalar (a [1, 1] corner of
        # a tile does not broadcast over both axes on the chip)
        last = jnp.sum(jnp.where(at_end, cum_c, 0.0))
        weight = dt_c * jnp.exp(last - cum_c)               # [Q, 1]
        new = lax.dot_general(
            xh, (bm.astype(f32) * weight).astype(xh.dtype),
            (((0,), (0,)), ((), ())), precision=precision,
            preferred_element_type=f32)                     # [P, N]
        state_ref[j] = jnp.exp(last) * state + new


@functools.partial(jax.jit, donate_argnums=(),
                   static_argnames=("chunk", "interpret", "precision"))
def ssd_scan_kernel(x, dt, a, b, c, d, *, chunk: int,
                    interpret: bool = False, precision=None):
    """The Pallas kernel.  Shapes as ``ssd_recurrence``; on the chip
    ``P`` and ``N`` are multiples of 128 and ``chunk`` of 8."""
    f32 = jnp.float32
    r, t, h, p = x.shape
    g, n = b.shape[2:]
    if t % chunk:
        raise ValueError(f"{t} positions are no multiple of the chunk {chunk}")
    nc, hg = t // chunk, h // g
    cum = _chunk_sums(dt, a, chunk)

    def columns(v):                       # [R, T, H] -> [R, G, T, hg]
        return v.astype(f32).reshape(r, t, g, hg).transpose(0, 2, 1, 3)

    def as_rows(v):                       # [R, T, H] -> [R, G, hg, T]
        return v.astype(f32).reshape(r, t, g, hg).transpose(0, 2, 3, 1)

    d_wide = jnp.repeat(d.astype(f32), p).reshape(g, 1, hg * p)
    wide = pl.BlockSpec((1, chunk, hg * p), lambda i, j, k: (i, k, j))
    shared = pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, j))
    column = pl.BlockSpec((1, 1, chunk, hg), lambda i, j, k: (i, j, k, 0))
    row = pl.BlockSpec((1, 1, hg, chunk), lambda i, j, k: (i, j, 0, k))
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, heads=hg, p=p, precision=precision),
        grid=(r, g, nc),
        in_specs=[wide, shared, shared, column, column, row, row,
                  pl.BlockSpec((1, 1, hg * p), lambda i, j, k: (j, 0, 0))],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct((r, t, h * p), x.dtype),
        scratch_shapes=[pltpu.VMEM((hg, p, n), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=NAME,
    )(x.reshape(r, t, h * p), b.reshape(r, t, g * n), c.reshape(r, t, g * n),
      columns(dt), columns(cum), as_rows(dt), as_rows(cum), d_wide)
    return y.reshape(r, t, h, p)


def ssd_scan(x, dt, a, b, c, d, *, chunk: int, precision=None,
             force: Optional[object] = None):
    """``y`` of the scan, in ``x``'s dtype: the kernel on the TPU, the
    chunked algebra in ``jax.numpy`` on any other platform.  ``force``
    is the tests' (``True``, ``"interpret"``, ``False``), as in
    ``ops/sepconv``."""
    if _on_tpu() if force is None else force:
        return ssd_scan_kernel(x, dt, a, b, c, d, chunk=chunk,
                               interpret=(force == "interpret"),
                               precision=precision)
    return ssd_chunked(x, dt, a, b, c, d, chunk, precision=precision)
