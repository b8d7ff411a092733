"""Traffic: the jobs a cell's caller runs, built from the seed.

A traffic MIX is a data file ``traffic/<name>.json`` (the name a cell
gives under ``traffic`` in ``BENCHMARK.json``).  Its ``generator`` key
names the module ``traffic/<generator>.py`` that reads it.  A generator
has one function::

    build(params, config, seed, workdir) -> Traffic

and a ``Traffic`` holds the job inputs that are used in turn, knows how
to run one job through the public API, and hands the plain reference
what it needs: the distinct images as the reference itself decodes
them, and which of them each row of each job input shows.  The stage a
job drives is the one the configuration names (``make_stage``).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple

import numpy as np


#: the column every stage under test writes its answer to
OUTPUT_COL = "features"


def make_stage(config: Dict[str, Any], batch_size: int):
    """The public stage that the configuration names under ``stage``
    (``DeepImageFeaturizer``, ``DeepImagePredictor``), over the
    configuration's model at ``batch_size``."""
    import sparkdl_tpu

    return getattr(sparkdl_tpu, config["stage"])(
        inputCol="image", outputCol=OUTPUT_COL,
        modelName=config["model_name"], batchSize=batch_size)


class JobResult(NamedTuple):
    frame: Any                 # the DataFrame ``transform`` returned
    #: seconds by layer on the benchmark's clock, in the order they ran
    #: and together the whole job
    spans: Dict[str, float]


class Traffic(NamedTuple):
    batch_size: int
    job_images: int
    inputs: List[Any]                               # used in turn
    #: a small input of the same kind: set-up runs one job over it, which
    #: compiles (or loads) the one dispatch shape the window uses
    warm_input: Any
    warm_images: int
    run_job: Callable[[Any], JobResult]             # drives the public API
    #: () -> uint8 RGB [D, h, w, 3]: the distinct images at the model's
    #: input size, decoded and resized by the REFERENCE's own code
    reference_images: Callable[[], np.ndarray]
    #: per job input: the distinct image that each row shows
    row_sources: List[np.ndarray]
    facts: Dict[str, Any]                           # for the record


def photo_like(rng: np.random.Generator, height: int, width: int
               ) -> np.ndarray:
    """A uint8 RGB image with a photograph's statistics rather than white
    noise's: a smooth low-frequency pattern plus mild sensor-like noise,
    so that a JPEG of it has a photo's file size and Huffman work."""
    from PIL import Image

    coarse = rng.integers(0, 256, (6, 8, 3), dtype=np.uint8)
    smooth = np.asarray(Image.fromarray(coarse, "RGB").resize(
        (width, height), Image.BICUBIC), dtype=np.float32)
    noisy = smooth + rng.normal(0.0, 6.0, smooth.shape).astype(np.float32)
    return np.clip(noisy, 0, 255).astype(np.uint8)
