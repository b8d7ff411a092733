"""Jobs from JPEG files: ``readImages(dir)`` then
``DeepImageFeaturizer(...).transform(df)``, as the reference's README
does to the flowers data.

Parameters (``traffic/<mix>.json``): ``image_height``/``image_width``
and ``jpeg_quality`` of the files, ``distinct_images`` encoded from the
seed, ``batch_size`` handed to the featurizer, ``job_batches`` (a job is
``job_batches x batch_size`` files), ``directories`` used in turn,
``num_partitions`` handed to ``readImages`` (1: a job's rows reach
``transform`` as one record batch and go out in dispatches full of real
rows; ``readImages``'s default is record batches of 256 rows, which
``transform`` does not join but pads one by one), ``warm_images`` files
in the directory that set-up's one warm job reads (the real rows of the
window's padded dispatch: the engine's slice of a dispatch's real rows
is a program of its own for every count).  Each directory shows the
distinct images in an order of its own, under distinct file names; a
repeated image is a hard link, so that a run writes each distinct file
once.
"""

from __future__ import annotations

import io
import os
import shutil
import time

import numpy as np

from benchmark import traffic as base
from benchmark.reference import images as ref_images


def build(params, config, seed, workdir) -> base.Traffic:
    from PIL import Image

    batch = int(params["batch_size"])
    job_images = int(round(float(params["job_batches"]) * batch))
    distinct = int(params["distinct_images"])
    h, w = int(params["image_height"]), int(params["image_width"])
    rng = np.random.default_rng([seed, 1])
    root = os.path.join(workdir, "jpeg_files")
    shutil.rmtree(root, ignore_errors=True)
    pool = os.path.join(root, "distinct")
    os.makedirs(pool)
    blobs = []
    for i in range(distinct):
        buf = io.BytesIO()
        Image.fromarray(base.photo_like(rng, h, w), "RGB").save(
            buf, format="JPEG", quality=int(params["jpeg_quality"]))
        blobs.append(buf.getvalue())
        with open(os.path.join(pool, f"{i:05d}.jpg"), "wb") as fh:
            fh.write(blobs[-1])
    partitions = int(params["num_partitions"])
    warm_images = int(params["warm_images"])
    warm_dir = os.path.join(root, "warm")
    os.makedirs(warm_dir)
    for row in range(warm_images):
        _link(os.path.join(pool, f"{row % distinct:05d}.jpg"),
              os.path.join(warm_dir, f"img_{row:05d}.jpg"))
    dirs, sources = [], []
    for d in range(int(params["directories"])):
        path = os.path.join(root, f"job_{d}")
        os.makedirs(path)
        order = np.concatenate([rng.permutation(distinct) for _ in range(
            -(-job_images // distinct))])[:job_images]
        for row, src in enumerate(order):
            _link(os.path.join(pool, f"{src:05d}.jpg"),
                  os.path.join(path, f"img_{row:05d}.jpg"))
        dirs.append(path)
        sources.append(order)          # readImages lists files by name
    mh, mw = int(config["input_height"]), int(config["input_width"])

    def run_job(directory) -> base.JobResult:
        from sparkdl_tpu import readImages

        t0 = time.perf_counter()
        df = readImages(directory, numPartitions=partitions)
        t1 = time.perf_counter()
        out = base.make_stage(config, batch).transform(df)
        return base.JobResult(out, {"decode": t1 - t0,
                                    "transform": time.perf_counter() - t1})

    return base.Traffic(
        batch_size=batch, job_images=job_images, inputs=dirs,
        warm_input=warm_dir, warm_images=warm_images, run_job=run_job,
        reference_images=lambda: ref_images.decode_resize_rgb(blobs, mh, mw),
        row_sources=sources,
        facts={"mean_file_bytes": float(np.mean([len(b) for b in blobs])),
               "distinct_images": distinct})


def _link(src: str, dst: str) -> None:
    try:
        os.link(src, dst)
    except OSError:
        shutil.copyfile(src, dst)
