"""Operations and bytes of a sequence model, from shapes.

``flops.py`` counts convolutions from a reference's layer table; a
trunk over rows of token ids is counted here, by the same convention:
2 x the multiply-accumulates of every matrix product at every position,
plus causal attention at its ``T (T + 1) / 2`` query-key pairs, plus the
state-space recurrence as it is written.  Norms, activations, the
rotary position, the depthwise convolution (8 operations a channel a
position) and the embedding's gather are left out: under 0.1% of a
block.  The number is pinned in the configuration's file under
``flops_per_image`` (operations a ROW; ``step_mfu`` reads it under that
name for every kind); ``benchmark/tests/test_sequence_flops.py`` holds the file
to this count and this count to one made by hand.

A kernel's roofline count does not depend on what implements the
kernel: the scan's operations are the recurrence's own, its bytes the
arrays the recurrence reads and writes, once, at their dtypes.
"""

from __future__ import annotations

from typing import Iterable, Optional, Tuple


def matmul_flops(shapes: Iterable[Tuple[int, int]], positions: int) -> int:
    """2 x multiply-accumulates of ``[in, out]`` matrices applied at
    every one of ``positions``."""
    return 2 * positions * sum(i * o for i, o in shapes)


def causal_attention_flops(heads: int, head_dim: int, positions: int) -> int:
    """Scores and weighted values (a multiply-accumulate each, over
    ``head_dim``) for every query head at every pair of a query and a
    key that is not after it."""
    return 4 * head_dim * heads * (positions * (positions + 1) // 2)


def causal_attention_bytes(heads: int, kv_heads: int, head_dim: int,
                           positions: int, itemsize: int) -> int:
    """``q`` and the output at ``heads``, ``k`` and ``v`` at ``kv_heads``,
    each read or written once."""
    return 2 * (heads + kv_heads) * head_dim * positions * itemsize


def scan_flops(heads: int, head_dim: int, state: int, positions: int) -> int:
    """The recurrence ``S = decay * S + dt x (outer) B; y = S C + D x`` as
    written: a multiply-accumulate an element of the ``[head_dim,
    state]`` state for the update, one for the read-out, and ``D x``."""
    return (4 * head_dim * state + 2 * head_dim) * heads * positions


def scan_bytes(heads: int, head_dim: int, groups: int, state: int,
               positions: int, itemsize: int, dt_itemsize: int = 4) -> int:
    """``x`` read and ``y`` written (``heads * head_dim``), ``B`` and
    ``C`` read (``groups * state`` each) at ``itemsize``, ``dt`` read (a
    number a head) at ``dt_itemsize``: once each."""
    return positions * (2 * heads * head_dim * itemsize
                        + 2 * groups * state * itemsize
                        + heads * dt_itemsize)


def roofline_seconds(flops: float, nbytes: float, peak) -> float:
    """The least time the chip could take: the larger of operations over
    the peak rate and bytes over the memory's (``peaks.json``'s row)."""
    return max(flops / float(peak["bf16_flops_per_s"]),
               nbytes / float(peak["hbm_bytes_per_s"]))


def kernel_seconds(obs, name: str) -> Optional[float]:
    """Device seconds of the Pallas kernel called ``name`` in the traced
    window, or ``None`` where it is not among the operations the
    reduction kept (``trace_reduce`` keeps the ten with the most
    seconds).  A ``pallas_call``'s ``name`` is its HLO instruction's
    name: the trace's line reads ``%<name>.<n> <shape> custom-call``."""
    if obs.trace is None:
        return None
    seconds = sum(s for op, s in obs.trace.device_ops
                  if op.split(" ")[0].split(".")[0] == f"%{name}")
    return seconds if seconds > 0 else None


def kernel_roofline_share(obs, name: str, flops_per_row_block: float,
                          bytes_per_row_block: float) -> Optional[float]:
    """Percent: the least time the chip could take for the kernel's
    calls of the window over the time they took.  The kernel runs once a
    block over every dispatched row, padding included
    (``engine.rows + engine.pad_rows``)."""
    seconds = kernel_seconds(obs, name)
    rows = (obs.counters.get("engine.rows", 0.0)
            + obs.counters.get("engine.pad_rows", 0.0))
    if seconds is None or rows <= 0:
        return None
    calls = rows * obs.config["num_hidden_layers"] / obs.chips
    return 100.0 * calls * roofline_seconds(
        flops_per_row_block, bytes_per_row_block, obs.peak) / seconds
