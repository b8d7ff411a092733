"""The kind ``block_diffusion``: a generator of the program
(``sparkdl_tpu.models.block_diffusion``) over rows of prompt ids, through
``sparkdl_tpu.TFTransformer`` — an int32 list column in, the columns
``generated`` and ``revealed_at`` (int32 lists) and ``features`` (a float
list) out — and nothing else; the seven answers, as ``expert_trunk``
gives them for the one-pass trunk.

Keys of the configuration's file that are this kind's: every key of the
published ``config.json`` (the program and the reference both read the
architecture from them), ``expert_share`` (``[0, 1]``: every expert of a
layer is held), ``prompt_length``, ``generated_length``,
``block_length``, ``denoise_steps`` and ``mask_token_id`` (the sampler's
settings, listed under ``assumed``), ``feature_size`` (three numbers a
generated position) and ``flops_per_image`` (operations a row, counted by
``diffusion_flops`` through the reference's ``flops_per_row``).
``compute_dtype`` and ``matmul_precision`` are handed to
``block_diffusion.model_function``; nothing is set in the environment.
The kind keeps ONE stage a batch size, as a user does.

**What is compared.**  With seeded weights the largest of 151,936 logits
changes on rounding, so ids cannot be compared with a reference that
generates freely.  ``features`` holds, a generated position, the logit
the program chose, the ``logsumexp`` and a zero; the reference REPLAYS
the program's own trajectory (``traffic.reference_images()`` hands over
``[D, P + 2 L]``: a row's prompt, the ids a capture of the stage
generated for it at set-up and the pass that revealed each) and reads
the largest logit, the ``logsumexp`` and how far the revealed position's
confidence fell short of the best one left masked.  The timed jobs
generate freely, so a row that does not repeat its captured trajectory
reads the logits of another sequence: ``feature_gap`` of order 1.

A reference module of this kind gives ``draw_weights(config, seed)`` (an
object that draws on the device, the same numbers at every call:
``embedding()``, ``leaf(layer, published name)``, ``final_norm()``,
``lm_head()``), ``replay(config, weights, prompts, generated,
revealed_at, operands=None)`` and ``flops_per_row(config)``.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, Optional

import numpy as np

from benchmark.flops import reference_module
from benchmark.harness import BenchmarkError, reference_of
# what holds for any program that takes its precision as an argument
from benchmark.kinds.token_trunk import STATEMENTS, check_stated_precision
from benchmark.traffic import OUTPUT_COL
from benchmark.traffic.prompt_rows import INPUT_COL

#: the program's outputs by the column each becomes
COLUMNS = {"generated": "generated", "revealed_at": "revealed_at",
           "features": OUTPUT_COL}
#: rows the reference replays together
REFERENCE_ROWS = 32


class _Program:
    """What ``install_weights`` gave the program, until it is freed."""
    model_function: Any = None
    stages: Dict[int, Any] = {}


def _generator():
    """The program's generator; a program from before it is refused
    before anything is drawn or measured."""
    try:
        from sparkdl_tpu.models import block_diffusion
    except ImportError as e:
        raise BenchmarkError(
            f"this program has no block diffusion: {e}") from None
    return block_diffusion


def program_environment(config: Dict[str, Any]) -> Dict[str, str]:
    """Nothing in the environment: the stated precision is an argument
    of ``block_diffusion.model_function``."""
    _generator()
    stated = (config["compute_dtype"], config["matmul_precision"])
    if stated not in STATEMENTS:
        raise BenchmarkError(f"{config['name']} states {stated}; the "
                             f"generator has {sorted(STATEMENTS)}")
    return {}


def to_program_variables(weights, config: Dict[str, Any]):
    """The reference's weights in the generator's own tree, drawn
    straight onto the device one weight at a time: the program's one
    copy."""
    block_diffusion = _generator()
    return {"embed_tokens": weights.embedding().block_until_ready(),
            **block_diffusion.stack_layers(weights.leaf, config),
            "norm": weights.final_norm(),
            "lm_head": weights.lm_head().block_until_ready()}


def install_weights(config: Dict[str, Any], seed: int):
    """The weights as the reference's own code draws them from the seed,
    given to the program (one copy on the device, in the stated dtype);
    returns the rule (``Weights``) by which the reference draws each
    layer again when it reaches it."""
    block_diffusion = _generator()
    ref = reference_module(reference_of(config))
    if config["flops_per_image"] != ref.flops_per_row(config):
        raise BenchmarkError(f"{config['name']}: flops_per_image is not the "
                             f"reference's count {ref.flops_per_row(config)}")
    weights = ref.draw_weights(config, seed)
    _Program.model_function = block_diffusion.model_function(
        config, to_program_variables(weights, config),
        generated_length=config["generated_length"],
        denoise_steps=config["denoise_steps"],
        compute_dtype=config["compute_dtype"],
        matmul_precision=config["matmul_precision"])
    _Program.stages = {}
    return weights


def make_stage(config: Dict[str, Any], batch_size: int):
    from sparkdl_tpu import TFTransformer

    if batch_size not in _Program.stages:
        _Program.stages[batch_size] = TFTransformer(
            modelFunction=_Program.model_function,
            inputMapping={INPUT_COL: "ids"}, outputMapping=COLUMNS,
            batchSize=batch_size)
    return _Program.stages[batch_size]


def engine(config: Dict[str, Any], traffic):
    return make_stage(config, traffic.batch_size).engine()


def free_program_state() -> None:
    """The stage (with its engine and the one copy of the weights), the
    function and the engine's jit cache go."""
    from sparkdl_tpu.parallel.engine import clear_engine_jit_cache

    _Program.model_function, _Program.stages = None, {}
    clear_engine_jit_cache()
    gc.collect()


def reference_outputs(config: Dict[str, Any], weights, rows: np.ndarray,
                      operands: Optional[str] = None) -> np.ndarray:
    """The plain reference's replay of the distinct rows ``[D, P + 2 L]``
    (prompt, generated ids, the pass that revealed each), a block of rows
    at a time, ``[D, 3 L]``.  ``operands`` makes it the CONTROL."""
    ref = reference_module(reference_of(config))
    p, length = config["prompt_length"], config["generated_length"]
    if rows.shape[1] != p + 2 * length:
        raise BenchmarkError(f"rows of {rows.shape[1]} ids are no prompt of "
                             f"{p} with a trajectory of {length}")
    return np.concatenate([
        ref.replay(config, weights, block[:, :p], block[:, p:p + length],
                   block[:, p + length:], operands=operands)
        for block in (rows[i:i + REFERENCE_ROWS]
                      for i in range(0, len(rows), REFERENCE_ROWS))])


__all__ = ["check_stated_precision", "engine", "free_program_state",
           "install_weights", "make_stage", "program_environment",
           "reference_outputs"]
