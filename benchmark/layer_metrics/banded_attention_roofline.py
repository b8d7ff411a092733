"""The attention kernel's share of its roofline where layers see a
window: the least time the chip could take for the attention of every
dispatched row, layer by layer at the pairs of the layer's BAND
(``expert_trunk_flops.banded_attention_pairs``: a ``sliding_attention``
layer's window, a ``full_attention`` layer's whole triangle; the held
heads; ``q``, ``k``, ``v`` and the output once at the memory's rate,
whichever is larger) over the device seconds of the kernel in the
traced window.  ``attention_roofline`` counts the whole triangle in
every layer and is not listed where layers have a window.  The
``pallas_call`` is called ``causal_attention``.  ``None`` where that line
is not among the ten operations the reduction keeps, or the
configuration names no kinds of layer."""

from benchmark import expert_trunk_flops as ef
from benchmark import sequence_flops as sf

KERNEL = "causal_attention"


def read(obs):
    c = obs.config
    seconds = sf.kernel_seconds(obs, KERNEL)
    rows = (obs.counters.get("engine.rows", 0.0)
            + obs.counters.get("engine.pad_rows", 0.0))
    if seconds is None or rows <= 0 or "layer_types" not in c:
        return None
    t, heads = c["sequence_length"], c["num_attention_heads"]
    nbytes = sf.causal_attention_bytes(heads, c["num_key_value_heads"],
                                       c["head_dim"], t, itemsize=2)
    least = sum(sf.roofline_seconds(
        ef.attention_flops(heads, c["head_dim"],
                           ef.banded_attention_pairs(t, window)),
        nbytes, obs.peak) for window in ef.layer_windows(c))
    return 100.0 * rows * least / obs.chips / seconds
