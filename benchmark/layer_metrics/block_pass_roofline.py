"""The generation loop's share of its roofline: the least time the chip
could take for one pass (``diffusion_flops.block_pass_bytes`` at the
memory's rate — the weights of the experts the program counted as
touched, attention's and the router's, the cache's filled part at the
mean block and, on the denoise passes' share, the head's —
``block_pass_flops`` at the bf16 peak, whichever is larger) over
``block_pass_ms``.  The count is a LOWER bound on what a pass moves, so
the share cannot pass 100%.  ``None`` where ``block_pass_ms`` is."""

from benchmark import diffusion_flops as df
from benchmark import sequence_flops as sf
from benchmark.layer_metrics import block_pass_ms


def read(obs):
    took_ms, passes = block_pass_ms.read(obs), df.passes_per_dispatch(obs)
    if took_ms is None or passes is None:
        return None
    every = passes["denoise"] + passes["commit"]
    head_share, rows = passes["denoise"] / every, df.dispatched_rows(obs)
    least = sf.roofline_seconds(
        df.block_pass_flops(obs.config, rows, head_share),
        df.block_pass_bytes(obs.config, rows,
                            passes["touched_experts"] / every, head_share),
        obs.peak)
    return 100.0 * least / obs.chips / (took_ms / 1e3)
