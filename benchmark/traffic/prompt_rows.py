"""Jobs over rows of prompt ids for a stage that GENERATES: a DataFrame
with one int32 list column, ``prompt``, of ``prompt_length`` ids a row,
through the stage the configuration's kind builds, one ``transform`` a
job; every row yields the configuration's ``generated_length`` ids.

Parameters of a mix: ``batch_size`` (the stage's ``batchSize``),
``job_batches`` (rows of a job over the batch size), ``distinct_rows``
drawn from the seed, ids uniform over the vocabulary but the
configuration's ``mask_token_id``, ``frames`` used in turn (each shows
the distinct rows in a seeded order of its own), and ``prompt_length``,
which has to be the configuration's.  The warm job is the first frame,
whole: a full dispatch.

**The capture.**  What a generator's ``features`` are compared with is a
replay of its own trajectory (``kinds/block_diffusion.py``), so
``build`` runs the stage ONCE over the distinct prompts, in the order
they were drawn, and keeps the ``generated`` and ``revealed_at`` columns:
``reference_images()`` hands the reference ``[D, P + 2 L]``, a row's
prompt with that trajectory.  It runs at set-up (after the weights are
installed, before the warm job) and compiles the window's one dispatch
shape.  The timed jobs generate freely, the same prompts in other
orders: they have to reveal the same ids in the same passes.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic as base

INPUT_COL = "prompt"
CAPTURED = ("generated", "revealed_at")


def _frame(ids: np.ndarray):
    import pyarrow as pa

    from sparkdl_tpu.frame import DataFrame

    rows, length = ids.shape
    offsets = pa.array(np.arange(rows + 1, dtype=np.int32) * length)
    return DataFrame(pa.table({INPUT_COL: pa.ListArray.from_arrays(
        offsets, pa.array(ids.reshape(-1)))}))


def build(params, config, seed, workdir) -> base.Traffic:
    from benchmark.harness import BenchmarkError

    batch = int(params["batch_size"])
    rows = int(round(float(params["job_batches"]) * batch))
    distinct, length = int(params["distinct_rows"]), int(params["prompt_length"])
    if length != config["prompt_length"]:
        raise BenchmarkError(f"the mix sends prompts of {length} ids, "
                             f"{config['name']} states {config['prompt_length']}")
    rng = np.random.default_rng([seed, 5])
    ids = rng.integers(0, config["vocab_size"] - 1, (distinct, length),
                       dtype=np.int32)
    ids += ids >= config["mask_token_id"]           # every id but the mask's
    frames, sources = [], []
    for _ in range(int(params["frames"])):
        order = np.concatenate([rng.permutation(distinct) for _ in range(
            -(-rows // distinct))])[:rows]
        frames.append(_frame(ids[order]))
        sources.append(order)

    def run_job(frame) -> base.JobResult:
        t0 = time.perf_counter()
        out = base.make_stage(config, batch).transform(frame)
        return base.JobResult(out, {"transform": time.perf_counter() - t0})

    captured = run_job(_frame(ids)).frame
    trajectory = [captured.column_to_numpy(name) for name in CAPTURED]
    if any(t.shape != (distinct, config["generated_length"])
           for t in trajectory):
        raise BenchmarkError(
            f"the capture returned {[t.shape for t in trajectory]}, not "
            f"{config['generated_length']} ids a row")
    return base.Traffic(
        batch_size=batch, job_images=rows, inputs=frames,
        warm_input=frames[0], warm_images=rows, run_job=run_job,
        reference_images=lambda: np.concatenate([ids] + trajectory, axis=1),
        row_sources=sources,
        facts={"distinct_rows": distinct, "prompt_ids_per_row": length,
               "generated_ids_per_row": config["generated_length"],
               "generated_ids_per_job": rows * config["generated_length"]})
