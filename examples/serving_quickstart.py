"""Online inference with sparkdl_tpu.serving, end to end.

The offline stack scores whole DataFrames; this example shows the ONLINE
path the serving subsystem adds: single requests admitted into a bounded
queue, assembled into dynamic micro-batches, dispatched through the same
InferenceEngine the transformers use, and demultiplexed back to
per-request futures — with deadlines, backpressure, and metrics.

Walkthrough:
  1. a raw ``fn(variables, batch)`` served with ``Server`` (threaded
     submitters, futures, p50/p99 from the metrics registry);
  2. asyncio integration (``predict_async``);
  3. ``serving.from_transformer``: a configured ``ModelTransformer``
     lifted into a server, with the server's rows checked bit-identical
     against the offline ``transform`` of the same inputs;
  4. the shared-queue UDF: ``register_serving_udf`` scores a DataFrame
     column THROUGH the running server.

Run:  python examples/serving_quickstart.py      (CPU, ~30 seconds)

This walkthrough PINS THE CPU BACKEND unless ``JAX_PLATFORMS`` is already
set: it documents the host-side API, and step 3 checks a server bucket
bit for bit against the offline batch — which holds between the CPU's
programs here and is not promised between differently shaped programs on
a chip (``chip_smoke.py`` makes that comparison there, to a stated
tolerance).  Export ``JAX_PLATFORMS=tpu`` to run it on a chip anyway.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import threading

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # see the header

from sparkdl_tpu import serving  # noqa: E402
from sparkdl_tpu.frame import DataFrame  # noqa: E402
from sparkdl_tpu.graph.function import ModelFunction  # noqa: E402
from sparkdl_tpu.transformers.tensor import ModelTransformer  # noqa: E402
from sparkdl_tpu.udf.registry import (register_serving_udf,  # noqa: E402
                                      udf_registry)

DIM, CLASSES = 32, 8


def make_model():
    rng = np.random.default_rng(7)
    variables = {"w": rng.normal(0, 0.2, (DIM, CLASSES)).astype(np.float32)}

    def fn(v, x):
        import jax.numpy as jnp

        logits = jnp.asarray(x, jnp.float32) @ v["w"]
        return jnp.exp(logits) / jnp.sum(jnp.exp(logits), axis=-1,
                                         keepdims=True)

    return fn, variables


def main():
    fn, variables = make_model()
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(96, DIM)).astype(np.float32)

    # -- 1. raw fn behind a server: concurrent submitters ----------------
    with serving.Server(fn, variables, max_batch_size=16, max_wait_ms=3,
                        max_queue=256) as srv:
        srv.warmup(xs[0])
        results = [None] * len(xs)

        def client(lo, hi):
            futs = [(i, srv.submit(xs[i])) for i in range(lo, hi)]
            for i, f in futs:
                results[i] = np.asarray(f.result())

        threads = [threading.Thread(target=client, args=(lo, lo + 24))
                   for lo in range(0, 96, 24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.stats()
        print(f"served {int(stats['serving.completed'])} requests in "
              f"{int(stats['serving.batches'])} micro-batches, p99 "
              f"{1e3 * stats['serving.request_latency.p99_s']:.1f} ms")

        # -- 2. asyncio handler form -------------------------------------
        async def handler():
            return await asyncio.gather(
                *[srv.predict_async(xs[i]) for i in range(4)])

        async_rows = asyncio.run(handler())
        assert len(async_rows) == 4

    # -- 3. transformer -> server, parity with the offline path ----------
    mf = ModelFunction(fn=fn, variables=variables)
    stage = ModelTransformer(inputCol="features", outputCol="probs",
                             modelFunction=mf, batchSize=16)
    df = DataFrame({"features": [row for row in xs]})
    offline = stage.transform(df).column_to_numpy("probs")
    # one bucket pinned to the stage's batch size: bit-identity is a
    # per-padded-shape contract (different bucket widths agree only to
    # XLA-refusion tolerance)
    with serving.from_transformer(stage, max_wait_ms=3,
                                  bucket_sizes=[16]) as srv:
        online = np.stack([np.asarray(srv.predict(x)) for x in xs])
        assert np.array_equal(online.astype(np.float32), offline), \
            "online rows must be bit-identical to transform()"

        # -- 4. DataFrame column scored THROUGH the running server -------
        register_serving_udf("probs_via_server", srv)
        scored = udf_registry.apply("probs_via_server", df, "features",
                                    "probs")
        udf_rows = scored.column_to_numpy("probs")
        assert np.allclose(udf_rows, offline, rtol=1e-6, atol=1e-7)

    print(json.dumps({"serving_quickstart": "ok",
                      "requests": int(stats["serving.completed"])}))


if __name__ == "__main__":
    main()
