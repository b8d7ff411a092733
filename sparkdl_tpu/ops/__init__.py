"""TPU kernels (pallas) for hot ops the XLA autofuser leaves on the table.

The zoo's compute path is plain jax/flax wherever XLA already emits
optimal code (dense convs ride the MXU untouched); this package holds the
exceptions — ops whose default lowering materializes avoidable HBM
traffic, rewritten as fused pallas kernels with reference-parity jax
fallbacks for CPU/debug: the separable convolutions of the CNN zoo
(``sepconv``), the two parts of a hybrid sequence block that have no
lowering worth having — the chunked state-space scan (``ssd``) and
causal grouped-query attention without the score matrix, over the whole
row or a window of it (``attention``) — an expert layer's products by
group over the tokens routed to each expert (``grouped_matmul``; the
module is imported by its own name, which its entry point shares), and
a generation loop's few queries against the key/value cache it carries,
read in place (``cache_attention``, imported by its own name too).
"""

from sparkdl_tpu.ops.attention import causal_attention
from sparkdl_tpu.ops.sepconv import (fused_sepconv_flat, pad_to_flat,
                                     sepconv_reference, unflatten)
from sparkdl_tpu.ops.ssd import ssd_scan

__all__ = ["causal_attention", "fused_sepconv_flat", "pad_to_flat",
           "sepconv_reference", "ssd_scan", "unflatten"]
