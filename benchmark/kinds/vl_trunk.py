"""The kind ``vl_trunk``: a vision-language trunk of the program
(``sparkdl_tpu.models.vl_trunk``) as a featurizer of video clips,
through ``sparkdl_tpu.TFTransformer`` over two columns — a clip's frames
as a uint8 list column (``pixels``) and its ids as an int32 list column
(``ids``) into the program's two inputs, ``features`` out — and nothing
else; the seven answers, as ``expert_trunk`` gives them for the trunks
over one column.

Keys of the configuration's file that are this kind's: every key of the
published ``config.json`` (the program and the reference both read the
architecture from them), ``vision_config`` (the tower's widths, listed
under ``assumed``), ``image_token_id``, ``vision_start_token_id``,
``vision_end_token_id``, ``expert_share`` (``[0, 1]``: every expert of a
layer is held), ``sequence_length``, ``frames`` and ``frame_size`` (a
row's clip), ``feature_size`` and ``flops_per_image`` (operations a row,
counted by ``vl_trunk_flops`` through the reference's ``flops_per_row``).
``compute_dtype`` and ``matmul_precision`` are handed to
``vl_trunk.model_function``; nothing is set in the environment.  The
kind keeps ONE stage a batch size, as a user does.

A reference module of this kind gives ``draw_weights(config, seed)`` (an
object that draws on the device, the same numbers at every call:
``embedding()``, ``leaf(layer, published name)``, ``vision_leaf(block,
published name)``, ``final_norm()``; built on the int32 pair
``seed_parts(seed)`` inside a program, it draws there, so the program
that draws takes the seed as an argument), ``forward(config, weights,
pixels, ids, operands=None)`` and ``flops_per_row(config)``.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from benchmark.flops import reference_module
from benchmark.harness import BenchmarkError, reference_of
# what holds for any program that takes its precision as an argument
from benchmark.kinds.token_trunk import STATEMENTS, check_stated_precision
from benchmark.traffic import OUTPUT_COL
from benchmark.traffic.video_rows import IDS_COL, PIXELS_COL


class _Program:
    """What ``install_weights`` gave the program, until it is freed."""
    model_function: Any = None
    stages: Dict[int, Any] = {}


def _trunk():
    """The program's trunk; a program from before it is refused before
    anything is drawn or measured."""
    try:
        from sparkdl_tpu.models import vision_tower, vl_trunk
    except ImportError as e:
        raise BenchmarkError(
            f"this program has no vision-language trunk: {e}") from None
    return vl_trunk, vision_tower


def program_environment(config: Dict[str, Any]) -> Dict[str, str]:
    """Nothing in the environment: the stated precision is an argument
    of ``vl_trunk.model_function``."""
    _trunk()
    stated = (config["compute_dtype"], config["matmul_precision"])
    if stated not in STATEMENTS:
        raise BenchmarkError(f"{config['name']} states {stated}; the trunk "
                             f"has {sorted(STATEMENTS)}")
    return {}


def to_program_variables(config: Dict[str, Any], seed: int):
    """The reference's weights in the trunk's own tree, drawn straight
    onto the device by ONE program that takes the seed as its argument
    (set-up loads one compiled draw whatever the seed), every stack
    filled in place: the program's one copy."""
    vl_trunk, vision_tower = _trunk()
    ref = reference_module(reference_of(config))

    def draw(parts):
        w = ref.draw_weights(config, parts)
        return {"vision": vision_tower.stack_tower(
                    w.vision_leaf, config["vision_config"],
                    config["hidden_size"]),
                "embed_tokens": w.embedding(),
                **vl_trunk.stack_layers(w.leaf, config),
                "norm": w.final_norm()}

    return jax.jit(draw)(ref.seed_parts(seed))


def install_weights(config: Dict[str, Any], seed: int):
    """The weights as the reference's own code draws them from the seed,
    given to the program (one copy on the device, in the stated dtype);
    returns the rule (``Weights``) by which the reference draws each
    weight again when it reaches it."""
    vl_trunk, _ = _trunk()
    ref = reference_module(reference_of(config))
    if config["flops_per_image"] != ref.flops_per_row(config):
        raise BenchmarkError(f"{config['name']}: flops_per_image is not the "
                             f"reference's count {ref.flops_per_row(config)}")
    _Program.model_function = vl_trunk.model_function(
        config, to_program_variables(config, seed),
        compute_dtype=config["compute_dtype"],
        matmul_precision=config["matmul_precision"])
    _Program.stages = {}
    return ref.draw_weights(config, seed)


def make_stage(config: Dict[str, Any], batch_size: int):
    from sparkdl_tpu import TFTransformer

    if batch_size not in _Program.stages:
        _Program.stages[batch_size] = TFTransformer(
            modelFunction=_Program.model_function,
            inputMapping={PIXELS_COL: "pixels", IDS_COL: "ids"},
            outputMapping={"features": OUTPUT_COL}, batchSize=batch_size)
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


def reference_outputs(config: Dict[str, Any], weights,
                      inputs: Tuple[np.ndarray, np.ndarray],
                      operands: Optional[str] = None) -> np.ndarray:
    """The plain reference over the distinct rows ``(pixels [D, frames x
    S x S x 3] uint8, ids [D, T])``: the tower over every row's frames a
    block at a time, then a row at a time through the layers, one
    layer's weights at a time.  ``operands`` makes it the CONTROL."""
    pixels, ids = inputs
    ref = reference_module(reference_of(config))
    return ref.forward(config, weights, pixels, ids, operands=operands)


__all__ = ["check_stated_precision", "engine", "free_program_state",
           "install_weights", "make_stage", "program_environment",
           "reference_outputs"]
