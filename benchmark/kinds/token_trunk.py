"""The kind ``token_trunk``: a sequence trunk of the program
(``sparkdl_tpu.models.hybrid_trunk``) as a featurizer over rows of
token ids, through ``sparkdl_tpu.ModelTransformer`` over an int32 list
column and nothing else.

Keys of the configuration's file that are this kind's: every key of the
published ``config.json`` (the program and the reference both read the
architecture from them), ``sequence_length`` (positions a row),
``feature_size`` and ``flops_per_image`` (operations a row, counted by
``sequence_flops`` through the reference's ``flops_per_row``).
``compute_dtype`` and ``matmul_precision`` are handed to
``hybrid_trunk.model_function``; nothing is set in the environment.  The
stage's engine is the one ``get_cached_engine`` keeps on it
(``ModelTransformer.engine``), so the kind keeps ONE stage a batch size
and every job gets that one: a user builds the stage once and calls
``transform`` on frame after frame.

A reference module of this kind gives ``draw_weights(config, seed)``
(an object that draws on the device, the same numbers at every call:
``embedding()``, ``leaf(block, published name)``, ``block(index)``,
``final_layernorm()``), ``forward(config, weights, ids, operands=None)``
and ``flops_per_row(config)``.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, Optional

import numpy as np

from benchmark.flops import reference_module
from benchmark.harness import BenchmarkError, reference_of
from benchmark.traffic import OUTPUT_COL
from benchmark.traffic.token_rows import INPUT_COL

STATEMENTS = {("bfloat16", "default"), ("float32", "highest")}


class _Program:
    """What ``install_weights`` gave the program, until it is freed."""
    model_function: Any = None
    stages: Dict[int, Any] = {}


def _trunk():
    """The program's trunk; a program from before it is refused before
    anything is drawn or measured."""
    try:
        from sparkdl_tpu.models import hybrid_trunk
    except ImportError as e:
        raise BenchmarkError(f"this program has no hybrid trunk: {e}") from None
    return hybrid_trunk


def program_environment(config: Dict[str, Any]) -> Dict[str, str]:
    """Nothing in the environment: the stated precision is an argument
    of ``hybrid_trunk.model_function``."""
    _trunk()
    stated = (config["compute_dtype"], config["matmul_precision"])
    if stated not in STATEMENTS:
        raise BenchmarkError(f"{config['name']} states {stated}; the trunk "
                             f"has {sorted(STATEMENTS)}")
    return {}


def check_stated_precision(config: Dict[str, Any], control: bool) -> None:
    import jax

    if control:
        raise BenchmarkError(f"{config['name']}: the trunk has no lower "
                             f"precision of its own for a control to switch on")
    if jax.config.jax_default_matmul_precision is not None:
        raise BenchmarkError(
            f"{config['name']} hands its precision to the function; the "
            f"process's default is "
            f"{jax.config.jax_default_matmul_precision!r}, not unset")


def to_program_variables(weights, depth: int):
    """The reference's weights in the trunk's own tree, drawn straight
    onto the device one weight at a time: the program's one copy."""
    hybrid_trunk = _trunk()
    return {"embedding": weights.embedding().block_until_ready(),
            "blocks": hybrid_trunk.stack_blocks(weights.leaf, depth),
            "final_layernorm": weights.final_layernorm()}


def install_weights(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights as the reference's own code draws them from the seed,
    given to the program (one copy on the device, in the stated dtype);
    returns the rule (``Weights``) by which the reference draws each
    block again when it reaches it."""
    hybrid_trunk = _trunk()
    ref = reference_module(reference_of(config))
    if config["flops_per_image"] != ref.flops_per_row(config):
        raise BenchmarkError(f"{config['name']}: flops_per_image is not the "
                             f"reference's count {ref.flops_per_row(config)}")
    weights = ref.draw_weights(config, seed)
    _Program.model_function = hybrid_trunk.model_function(
        config, to_program_variables(weights, config["num_hidden_layers"]),
        compute_dtype=config["compute_dtype"],
        matmul_precision=config["matmul_precision"])
    _Program.stages = {}
    return weights


def make_stage(config: Dict[str, Any], batch_size: int):
    from sparkdl_tpu import ModelTransformer

    if batch_size not in _Program.stages:
        _Program.stages[batch_size] = ModelTransformer(
            inputCol=INPUT_COL, outputCol=OUTPUT_COL,
            modelFunction=_Program.model_function, batchSize=batch_size)
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


def reference_outputs(config: Dict[str, Any], weights: Dict[str, Any],
                      ids: np.ndarray, operands: Optional[str] = None
                      ) -> np.ndarray:
    """The plain reference over the distinct rows ``ids`` ``[D, T]``: one
    block's weights at a time on the device, one row at a time through
    it.  ``operands`` makes it the CONTROL."""
    ref = reference_module(reference_of(config))
    return ref.forward(config, weights, ids, operands=operands)
