"""Jobs over video clips with their text: a DataFrame with two list
columns, ``pixels`` (uint8, a row's ``clip_frames`` frames of
``frame_size`` x ``frame_size`` x 3 RGB, flat) and ``ids`` (int32,
``sequence_length`` ids), through the stage the configuration's kind
builds, one ``transform`` a job.

A row's ids are ``text_ids`` text ids (a transcript and a question,
uniform over the vocabulary but the three vision ids), then the clip:
each frame a ``vision_start_token_id``, as many ``image_token_id`` as
the tower gives a frame tokens, a ``vision_end_token_id``.  Their sum has
to be ``sequence_length``, the configuration's.  The pixels are uniform
over 0..255, drawn from the seed in bulk.

Parameters of a mix: ``batch_size`` (the stage's ``batchSize``),
``job_batches`` (rows of a job over the batch size), ``distinct_rows``
drawn from the seed, ``frames`` used in turn (each shows the distinct
rows in a seeded order of its own), ``warm_rows`` of the first frame for
set-up's one warm job, ``sequence_length``, ``text_ids``,
``clip_frames`` and ``frame_size`` (the last two the configuration's
``frames`` and ``frame_size``).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic as base

PIXELS_COL = "pixels"
IDS_COL = "ids"


def tokens_per_frame(config) -> int:
    v = config["vision_config"]
    return (v["image_size"] // v["patch_size"]
            // v["spatial_merge_size"]) ** 2


def row_ids(rng: np.random.Generator, config, text: int, clip_frames: int
            ) -> np.ndarray:
    """One row's ids: the text, then each frame bracketed."""
    special = sorted((config["image_token_id"], config["vision_start_token_id"],
                      config["vision_end_token_id"]))
    words = rng.integers(0, config["vocab_size"] - len(special), text,
                         dtype=np.int32)
    for s in special:                       # every id but the three
        words += words >= s
    frame = np.concatenate([[config["vision_start_token_id"]],
                            np.full(tokens_per_frame(config),
                                    config["image_token_id"]),
                            [config["vision_end_token_id"]]]).astype(np.int32)
    return np.concatenate([words, np.tile(frame, clip_frames)])


def _frame(pixels: np.ndarray, ids: np.ndarray):
    import pyarrow as pa

    from sparkdl_tpu.frame import DataFrame

    def column(values):
        rows, width = values.shape
        offsets = pa.array(np.arange(rows + 1, dtype=np.int32) * width)
        return pa.ListArray.from_arrays(offsets, pa.array(values.reshape(-1)))

    return DataFrame(pa.table({PIXELS_COL: column(pixels),
                               IDS_COL: column(ids)}))


def build(params, config, seed, workdir) -> base.Traffic:
    from benchmark.harness import BenchmarkError

    batch = int(params["batch_size"])
    rows = int(round(float(params["job_batches"]) * batch))
    distinct, length = int(params["distinct_rows"]), int(params["sequence_length"])
    text, clip = int(params["text_ids"]), int(params["clip_frames"])
    size = int(params["frame_size"])
    if (length, clip, size) != (config["sequence_length"], config["frames"],
                                config["frame_size"]):
        raise BenchmarkError(
            f"the mix sends rows of {length} ids and {clip} frames of "
            f"{size}; {config['name']} states {config['sequence_length']}, "
            f"{config['frames']} of {config['frame_size']}")
    if text + clip * (tokens_per_frame(config) + 2) != length:
        raise BenchmarkError(f"{text} text ids and {clip} frames of "
                             f"{tokens_per_frame(config)} tokens are not "
                             f"{length} ids")
    rng = np.random.default_rng([seed, 6])
    ids = np.stack([row_ids(rng, config, text, clip) for _ in range(distinct)])
    pixels = rng.integers(0, 256, (distinct, clip * size * size * 3),
                          dtype=np.uint8)
    frames, sources = [], []
    for _ in range(int(params["frames"])):
        order = np.concatenate([rng.permutation(distinct) for _ in range(
            -(-rows // distinct))])[:rows]
        frames.append(_frame(pixels[order], ids[order]))
        sources.append(order)

    def run_job(frame) -> base.JobResult:
        t0 = time.perf_counter()
        out = base.make_stage(config, batch).transform(frame)
        return base.JobResult(out, {"transform": time.perf_counter() - t0})

    warm_rows = int(params["warm_rows"])
    return base.Traffic(
        batch_size=batch, job_images=rows, inputs=frames,
        warm_input=_frame(pixels[sources[0][:warm_rows]],
                          ids[sources[0][:warm_rows]]),
        warm_images=warm_rows, run_job=run_job,
        reference_images=lambda: (pixels, ids), row_sources=sources,
        facts={"distinct_rows": distinct, "tokens_per_row": length,
               "text_ids_per_row": text, "frames_per_row": clip,
               "image_tokens_per_row": clip * tokens_per_frame(config),
               "pixel_bytes_per_row": clip * size * size * 3,
               "tokens_per_job": rows * length})
