"""The kind ``expert_trunk``: a sparse-expert sequence trunk of the
program (``sparkdl_tpu.models.expert_trunk``) as a featurizer over rows
of token ids, through ``sparkdl_tpu.ModelTransformer`` over an int32
list column and nothing else — what ``token_trunk`` is for the hybrid
trunk, with the same seven answers.

Keys of the configuration's file that are this kind's: every key of the
published ``config.json`` (the program and the reference both read the
architecture from them; the head counts and ``num_experts`` are what is
HELD here), ``expert_share`` (``[which, of how many]`` chips that share
a layer), ``sequence_length``, ``feature_size`` and ``flops_per_image``
(operations a row, counted by ``expert_trunk_flops`` through the
reference's ``flops_per_row``; the routed experts at their expected
pairs).  ``compute_dtype`` and ``matmul_precision`` are handed to
``expert_trunk.model_function``; nothing is set in the environment.  The
kind keeps ONE stage a batch size, as a user does.

A reference module of this kind gives ``draw_weights(config, seed)`` (an
object that draws on the device, the same numbers at every call:
``embedding()``, ``leaf(layer, published name)``, ``final_layernorm()``),
``forward(config, weights, ids, operands=None)`` and
``flops_per_row(config)``.
"""

from __future__ import annotations

import gc
from typing import Any, Dict

from benchmark.flops import reference_module
from benchmark.harness import BenchmarkError, reference_of
# what holds for any trunk that takes its precision as an argument and
# whose reference runs over rows of ids
from benchmark.kinds.token_trunk import (STATEMENTS, check_stated_precision,
                                         reference_outputs)
from benchmark.traffic import OUTPUT_COL
from benchmark.traffic.token_rows import INPUT_COL


class _Program:
    """What ``install_weights`` gave the program, until it is freed."""
    model_function: Any = None
    stages: Dict[int, Any] = {}


def _trunk():
    """The program's trunk; a program from before it is refused before
    anything is drawn or measured."""
    try:
        from sparkdl_tpu.models import expert_trunk
    except ImportError as e:
        raise BenchmarkError(f"this program has no expert trunk: {e}") from None
    return expert_trunk


def program_environment(config: Dict[str, Any]) -> Dict[str, str]:
    """Nothing in the environment: the stated precision is an argument
    of ``expert_trunk.model_function``."""
    _trunk()
    stated = (config["compute_dtype"], config["matmul_precision"])
    if stated not in STATEMENTS:
        raise BenchmarkError(f"{config['name']} states {stated}; the trunk "
                             f"has {sorted(STATEMENTS)}")
    return {}


def to_program_variables(weights, config: Dict[str, Any]):
    """The reference's weights in the trunk's own tree, drawn straight
    onto the device one weight at a time: the program's one copy."""
    expert_trunk = _trunk()
    return {"embedding": weights.embedding().block_until_ready(),
            **expert_trunk.stack_layers(weights.leaf, config),
            "final_layernorm": weights.final_layernorm()}


def install_weights(config: Dict[str, Any], seed: int):
    """The weights as the reference's own code draws them from the seed,
    given to the program (one copy on the device, in the stated dtype);
    returns the rule (``Weights``) by which the reference draws each
    layer again when it reaches it."""
    expert_trunk = _trunk()
    ref = reference_module(reference_of(config))
    if config["flops_per_image"] != ref.flops_per_row(config):
        raise BenchmarkError(f"{config['name']}: flops_per_image is not the "
                             f"reference's count {ref.flops_per_row(config)}")
    weights = ref.draw_weights(config, seed)
    _Program.model_function = expert_trunk.model_function(
        config, to_program_variables(weights, config),
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


__all__ = ["check_stated_precision", "engine", "free_program_state",
           "install_weights", "make_stage", "program_environment",
           "reference_outputs"]
