"""Capture an xplane device trace of one zoo-model forward (the round-4
committed artifact's recipe, parameterized) — run on the chip machine
to refresh `artifacts/profile_r*/`; without an accelerator it fails.

Usage: python tools/capture_profile.py [model] [out_dir] [batch]
       (defaults: InceptionV3 artifacts/profile_r05 128)

Writes `<out_dir>/<model>/...xplane.pb` (XProf/TensorBoard-viewable) plus
any trace.json.gz jax emits, and prints one JSON line with the in-trace
wall time.  The model runs through the bench configuration (bf16 compute,
fused preprocess, batch on device) so the trace matches the headline
program, including the round-5 fused branch heads when the env enables
them (default on).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    model = sys.argv[1] if len(sys.argv) > 1 else "InceptionV3"
    out = sys.argv[2] if len(sys.argv) > 2 else "artifacts/profile_r05"
    batch = int(sys.argv[3]) if len(sys.argv) > 3 else 128

    import jax

    import bench
    from sparkdl_tpu.parallel import compile_cache
    from sparkdl_tpu.utils.metrics import Metrics

    compile_cache.configure_default()
    device = bench.require_accelerator()  # a CPU trace is not a profile
    fn, variables, (h, w) = bench._zoo_fn(model, featurize=True)
    # no donation: the same device batch is re-dispatched every profile
    # iteration below
    g = jax.jit(fn, donate_argnums=())
    rng = np.random.default_rng(0)
    x = jax.device_put(
        (rng.random((batch, h, w, 3)) * 255).astype(np.uint8))
    jax.block_until_ready(g(variables, x))  # compile outside the trace

    trace_dir = os.path.join(out, model.lower())
    os.makedirs(trace_dir, exist_ok=True)
    m = Metrics()
    t0 = time.perf_counter()
    with m.profile(trace_dir, block_on=None):
        out_dev = g(variables, x)
        jax.block_until_ready(out_dev)
    wall = time.perf_counter() - t0
    print(json.dumps({
        "model": model, "batch": batch, "trace_dir": trace_dir,
        "in_trace_wall_s": round(wall, 4),
        "implied_img_s": round(batch / wall, 1), "device": device}))


if __name__ == "__main__":
    main()
