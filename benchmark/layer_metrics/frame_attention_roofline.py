"""The vision tower's attention kernel's share of its roofline: the least
time the chip could take for attention both ways over every frame the
window's rows sent through the tower, every block
(``vl_trunk_flops.frame_attention_flops``: ``4 P^2 D`` a frame-block, at
the bf16 peak; ``frame_attention_bytes``: ``q``, ``k``, ``v`` and the
output once, at the memory's rate — whichever is larger) over the device
seconds of the kernel in the traced window.  The frames and patches are
the program's own counts (``vision.frames``, ``vision.patches``), scaled
to the dispatched rows.  The ``pallas_call`` is called
``frame_attention``: the trace's line reads ``%frame_attention.<n>
<shape> custom-call``.  ``None`` where that line is not among the ten
operations the reduction keeps, or the program counts no frames."""

from benchmark import sequence_flops as sf
from benchmark import vl_trunk_flops as vf
from benchmark.layer_metrics.indexer_roofline import dispatched

KERNEL = "frame_attention"


def read(obs):
    c = obs.config
    seconds = sf.kernel_seconds(obs, KERNEL)
    frames = dispatched(obs, "vision.frames")
    if seconds is None or frames is None or "vision_config" not in c:
        return None
    patches = obs.counters.get("vision.patches", 0.0) / obs.counters[
        "vision.frames"]
    least = sf.roofline_seconds(
        vf.frame_attention_flops(c, frames, patches),
        vf.frame_attention_bytes(c, frames, patches), obs.peak)
    return 100.0 * least / obs.chips / seconds
