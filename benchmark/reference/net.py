"""The plain reference's building blocks: convolution, inference batch
norm, pooling, and the one object (``Net``) that hands an architecture
its weights.

Everything here is straightforward ``jax.numpy``/``lax`` in float32: no
kernels, no fusion, no folding of batch norm into a kernel, no batching
tricks.  It imports nothing of ``sparkdl_tpu`` and takes nothing that
the program made: the weights are drawn here, from the seed, and handed
to the program (``to_program_variables`` in each architecture's file),
never the other way round.

An architecture is a function ``forward(net, x)`` that calls
``net.conv`` / ``net.bn`` by layer name.  The same function serves
three purposes, so the three cannot drift apart:

* ``declare(forward, input_shape)`` walks it under ``jax.eval_shape``
  (nothing is computed) and returns every parameter's shape and every
  convolution's geometry — what ``flops.py`` counts from;
* ``draw_weights(params, key)`` draws all parameters in ONE jitted call
  on the device;
* ``Net(weights)`` computes.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

Padding = Union[str, Sequence[Tuple[int, int]]]


class ConvGeometry(NamedTuple):
    """One convolution as the FLOP count needs it."""
    name: str
    kh: int
    kw: int
    cin: int
    cout: int
    out_h: int
    out_w: int


class Declared(NamedTuple):
    #: name -> (kind, shape, epsilon of its batch norm or None)
    params: Dict[str, Tuple[str, Tuple[int, ...], Optional[float]]]
    convs: List[ConvGeometry]
    output_shape: Tuple[int, ...]


class Net:
    """Hands ``forward`` its parameters by name.  With ``weights=None``
    it only records shapes (use under ``jax.eval_shape``)."""

    def __init__(self, weights: Optional[Dict[str, jnp.ndarray]] = None,
                 precision=None, operands: Optional[str] = None):
        self.weights = weights
        self.precision = precision
        #: None, or the lower precision a CONTROL holds every
        #: convolution's operands in (``OPERANDS``)
        self.operands = OPERANDS[operands] if operands else None
        self.params: Dict[str, Tuple[str, Tuple[int, ...],
                                     Optional[float]]] = {}
        self.convs: List[ConvGeometry] = []

    def _param(self, name: str, kind: str, shape: Tuple[int, ...],
               eps: Optional[float] = None):
        if name in self.params:
            raise ValueError(f"parameter {name!r} declared twice")
        self.params[name] = (kind, shape, eps)
        if self.weights is None:
            return jnp.zeros(shape, jnp.float32)
        w = self.weights[name]
        if tuple(w.shape) != tuple(shape):
            raise ValueError(f"{name}: weight {w.shape}, layer wants {shape}")
        return w

    def conv(self, name: str, x, cout: int, kh: int, kw: int, *,
             stride: int = 1, padding: Padding = "SAME",
             bias: bool = False):
        cin = x.shape[-1]
        kernel = self._param(f"{name}/kernel", "conv_kernel",
                             (kh, kw, cin, cout))
        if self.operands is not None:
            x, kernel = self.operands(x), self.operands(kernel)
        y = lax.conv_general_dilated(
            x, kernel, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=self.precision)
        if bias:
            y = y + self._param(f"{name}/bias", "conv_bias", (cout,))
        self.convs.append(ConvGeometry(name, kh, kw, cin, cout,
                                       y.shape[1], y.shape[2]))
        return y

    def bn(self, name: str, x, *, eps: float, scale: bool = True):
        """Inference batch norm: (x - mean) / sqrt(var + eps) * gamma + beta."""
        c = x.shape[-1]
        mean = self._param(f"{name}/mean", "bn_mean", (c,))
        var = self._param(f"{name}/var", "bn_var", (c,), eps)
        beta = self._param(f"{name}/beta", "bn_beta", (c,))
        y = (x - mean) / jnp.sqrt(var + eps)
        if scale:
            y = y * self._param(f"{name}/gamma", "bn_gamma", (c,))
        return y + beta


def relu(x):
    return jnp.maximum(x, 0.0)


def as_int8(x):
    """``x`` held in int8: one symmetric scale for the tensor, values
    rounded to the 255 steps, then read back as float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def as_bfloat16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


#: what a control may hold a convolution's operands in
OPERANDS = {"int8": as_int8, "bfloat16": as_bfloat16}


def max_pool(x, window: int, stride: int, padding: Padding = "VALID"):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, window, window, 1),
                             (1, stride, stride, 1), _pool_padding(padding))


def avg_pool_same_excluding_pad(x, window: int):
    """TensorFlow's AvgPool with SAME padding and stride 1: each output
    is the mean of the window's pixels that lie INSIDE the image."""
    dims, strides = (1, window, window, 1), (1, 1, 1, 1)
    total = lax.reduce_window(x, 0.0, lax.add, dims, strides, "SAME")
    count = lax.reduce_window(jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype),
                              0.0, lax.add, dims, strides, "SAME")
    return total / count


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))


def _pool_padding(padding: Padding):
    if isinstance(padding, str):
        return padding
    return ((0, 0),) + tuple(tuple(p) for p in padding) + ((0, 0),)


def declare(forward, input_shape: Tuple[int, ...]) -> Declared:
    """Shapes of every parameter and convolution of ``forward`` for an
    input of ``input_shape`` (NHWC float32); computes nothing."""
    net = Net()
    out = jax.eval_shape(lambda x: forward(net, x),
                         jax.ShapeDtypeStruct(input_shape, jnp.float32))
    return Declared(dict(net.params), list(net.convs), tuple(out.shape))


def _power_of_two(key, shape):
    """2, 1 or 1/2 with probabilities 0.1, 0.5, 0.4: mean square 1."""
    u = jax.random.uniform(key, shape, jnp.float32)
    return jnp.where(u < 0.1, 2.0, jnp.where(u < 0.6, 1.0, 0.5))


def _draw(kind: str, key, shape, eps):
    """The weight distributions (``assumed`` in the configuration files).
    He-normal kernels keep the activations' scale through the ReLUs.  The
    batch-norm statistics are NOT the identity, so a wrong fold or a
    dropped mean or variance shows; but gamma and 1/sqrt(var + eps) are
    powers of two (2, 1, 1/2).  Scaling by a power of two is exact in
    floating point, so a program that folds the batch-norm scale into
    the kernel rounds that kernel to bf16 exactly as the plain reference
    rounds the unfolded one, and the comparison reads the precision of
    the computation, not the place of one multiplication."""
    if kind == "conv_kernel":
        fan_in = shape[0] * shape[1] * shape[2]
        return jax.random.normal(key, shape, jnp.float32) * (2.0 / fan_in) ** 0.5
    if kind == "conv_bias":
        return 0.05 * jax.random.normal(key, shape, jnp.float32)
    if kind in ("bn_beta", "bn_mean"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "bn_gamma":
        return _power_of_two(key, shape)
    if kind == "bn_var":
        return 1.0 / _power_of_two(key, shape) ** 2 - eps
    raise ValueError(f"unknown parameter kind {kind!r}")


def draw_weights(params: Dict[str, Tuple[str, Tuple[int, ...],
                                        Optional[float]]],
                 seed: int) -> Dict[str, jnp.ndarray]:
    """All parameters from the seed, in one jitted call on the device.
    Each parameter's key is the seed's key folded with a checksum of its
    NAME, so a weight does not depend on the order of declaration."""
    names = sorted(params)

    @jax.jit
    def draw(key):
        return {n: _draw(params[n][0],
                         jax.random.fold_in(key, zlib.crc32(n.encode())),
                         params[n][1], params[n][2]) for n in names}

    # any whole number a little over 2**31 is a valid seed: fold its high
    # and low halves in separately (PRNGKey alone takes 32 signed bits)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return draw(key)
