"""Jobs over rows of token ids: a DataFrame with one int32 list column,
``tokens``, of ``sequence_length`` ids a row, through the stage the
configuration's kind builds, one ``transform`` a job.

Parameters of a mix: ``batch_size`` (the stage's ``batchSize``),
``job_batches`` (rows of a job over the batch size: 2.5 leaves a last
dispatch half full, as a partition that is no multiple of the batch
does), ``distinct_rows`` drawn from the seed, ids uniform over the whole
vocabulary, ``frames`` used in turn (each shows the distinct rows in a
seeded order of its own), ``warm_rows`` of the first frame for set-up's
one warm job (the real rows of the padded dispatch), and
``sequence_length``, which has to be the configuration's.  A single
length is what a pipeline sends that cuts documents into fixed windows;
rows of uneven length are not a mix of this generator.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import traffic as base

INPUT_COL = "tokens"


def build(params, config, seed, workdir) -> base.Traffic:
    import pyarrow as pa

    from benchmark.harness import BenchmarkError
    from sparkdl_tpu.frame import DataFrame

    batch = int(params["batch_size"])
    rows = int(round(float(params["job_batches"]) * batch))
    distinct, length = int(params["distinct_rows"]), int(params["sequence_length"])
    if length != config["sequence_length"]:
        raise BenchmarkError(f"the mix sends rows of {length} ids, "
                             f"{config['name']} states {config['sequence_length']}")
    rng = np.random.default_rng([seed, 4])
    ids = rng.integers(0, config["vocab_size"], (distinct, length),
                       dtype=np.int32)
    frames, sources = [], []
    for _ in range(int(params["frames"])):
        order = np.concatenate([rng.permutation(distinct) for _ in range(
            -(-rows // distinct))])[:rows]
        flat = pa.array(ids[order].reshape(-1))
        offsets = pa.array(np.arange(rows + 1, dtype=np.int32) * length)
        frames.append(DataFrame(pa.table(
            {INPUT_COL: pa.ListArray.from_arrays(offsets, flat)})))
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
        reference_images=lambda: ids, row_sources=sources,
        facts={"distinct_rows": distinct, "tokens_per_row": length,
               "tokens_per_job": rows * length})
