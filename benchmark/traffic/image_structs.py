"""Jobs from a DataFrame of already decoded image structs at the
model's input size: ``DeepImageFeaturizer(...).transform(df)`` over the
reference's image schema, as a user holds it after ``readImages`` +
``createResizeImageUDF`` and reuses it across featurizers.

Parameters (``traffic/<mix>.json``): ``batch_size`` handed to the
featurizer, ``job_batches`` (a frame has ``job_batches x batch_size``
rows), ``distinct_images`` drawn from the seed (at the model's input
size, which the configuration gives), ``frames`` used in turn,
``warm_rows`` of the first frame for set-up's one warm job (the real
rows of the window's padded dispatch, as in ``jpeg_files``).  Each frame
shows the distinct images in an order of its own.  The frames are built
in set-up.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic as base

CV_8UC3 = 16      # the image schema's mode of a 3-channel uint8 image


def build(params, config, seed, workdir) -> base.Traffic:
    import pyarrow as pa

    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.image.schema import imageSchema

    batch = int(params["batch_size"])
    rows = int(round(float(params["job_batches"]) * batch))
    distinct = int(params["distinct_images"])
    h, w = int(config["input_height"]), int(config["input_width"])
    rng = np.random.default_rng([seed, 2])
    rgb = np.stack([base.photo_like(rng, h, w) for _ in range(distinct)])
    # the schema stores OpenCV's order: B, G, R
    bgr = [np.ascontiguousarray(img[:, :, ::-1]).tobytes() for img in rgb]
    frames, sources = [], []
    for f in range(int(params["frames"])):
        order = np.concatenate([rng.permutation(distinct) for _ in range(
            -(-rows // distinct))])[:rows]
        fields = {
            "origin": pa.array([f"frame{f}/row{r:06d}" for r in range(rows)],
                               pa.string()),
            "height": pa.array(np.full(rows, h, np.int32)),
            "width": pa.array(np.full(rows, w, np.int32)),
            "nChannels": pa.array(np.full(rows, 3, np.int32)),
            "mode": pa.array(np.full(rows, CV_8UC3, np.int32)),
            "data": pa.array([bgr[i] for i in order], pa.binary()),
        }
        column = pa.StructArray.from_arrays(
            [fields[fld.name].cast(fld.type) for fld in imageSchema],
            fields=list(imageSchema))
        frames.append(DataFrame(pa.table({"image": column})))
        sources.append(order)

    def run_job(frame) -> base.JobResult:
        t0 = time.perf_counter()
        out = base.make_stage(config, batch).transform(frame)
        return base.JobResult(out, {"transform": time.perf_counter() - t0})

    warm_rows = int(params["warm_rows"])
    return base.Traffic(
        batch_size=batch, job_images=rows, inputs=frames,
        warm_input=DataFrame(frames[0].table.slice(0, warm_rows)),
        warm_images=warm_rows, run_job=run_job,
        reference_images=lambda: rgb, row_sources=sources,
        facts={"distinct_images": distinct,
               "frame_bytes": rows * h * w * 3})
