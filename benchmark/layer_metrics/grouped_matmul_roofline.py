"""The expert kernel's share of its roofline: the least time the chip
could take for the token-expert pairs the window computed
(``expert_trunk_flops.grouped_matmul_flops`` at the bf16 peak;
``grouped_matmul_bytes`` at the memory's rate — the held experts'
matrices once a layer a dispatch, a pair's input and output rows once —
whichever is larger) over the device seconds of the kernel in the
traced window.  The pairs are the program's own count (``moe.pairs``,
the real rows'), scaled to the dispatched rows, padding included; a
dispatch is one execution of the program in the trace.  The
``pallas_call`` is called ``grouped_matmul``: the trace's line reads
``%grouped_matmul.<n> <shape> custom-call``.  ``None`` where that line is
not among the ten operations the reduction keeps, or the program counts
no pairs."""

from benchmark import expert_trunk_flops as ef
from benchmark import sequence_flops as sf

KERNEL = "grouped_matmul"


def dispatched_pairs(obs):
    """``moe.pairs`` scaled from the real rows to all dispatched rows,
    or ``None`` where the program counts none."""
    rows = obs.counters.get("engine.rows", 0.0)
    pairs = obs.counters.get("moe.pairs", 0.0)
    if rows <= 0 or pairs <= 0:
        return None
    return pairs * (rows + obs.counters.get("engine.pad_rows", 0.0)) / rows


def read(obs):
    c = obs.config
    seconds = sf.kernel_seconds(obs, KERNEL)
    pairs = dispatched_pairs(obs)
    if seconds is None or pairs is None or "moe_intermediate_size" not in c:
        return None
    layer_calls = obs.trace.module_executions * (
        c["num_hidden_layers"] - c["num_dense_layers"])
    least = sf.roofline_seconds(
        ef.grouped_matmul_flops(c, pairs),
        ef.grouped_matmul_bytes(c, pairs, layer_calls), obs.peak)
    return 100.0 * least / obs.chips / seconds
