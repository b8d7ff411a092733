"""The causal attention kernel's share of its roofline: the least time
the chip could take for the attention of every dispatched row in every
block (``sequence_flops.causal_attention_flops`` at the bf16 peak, ``q``,
``k``, ``v`` and the output once at the memory's rate, whichever is
larger) over the device seconds of the kernel in the traced window.
The ``pallas_call`` is called ``causal_attention``: the trace's line
reads ``%causal_attention.<n> <shape> custom-call``.  ``None`` where
that line is not among the ten operations the reduction keeps, or the
configuration has no attention heads."""

from benchmark import sequence_flops as sf

KERNEL = "causal_attention"


def read(obs):
    c = obs.config
    if "num_key_value_heads" not in c:
        return None
    t = c["sequence_length"]
    return sf.kernel_roofline_share(
        obs, KERNEL,
        sf.causal_attention_flops(c["num_attention_heads"], c["head_dim"], t),
        sf.causal_attention_bytes(c["num_attention_heads"],
                                  c["num_key_value_heads"], c["head_dim"], t,
                                  itemsize=2))
