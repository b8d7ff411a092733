"""Round-5 perf experiments (VERDICT r4 #1/#2): whole-model A/B runs on
the real chip, one JSON line per experiment.

Levers measured (results recorded in PERF.md):
  * Xception entry-flow row-tiled pallas kernel (SPARKDL_XC_TILED=1 vs 0)
  * InceptionV3 fused branch heads (SPARKDL_FUSED_HEADS=1 vs 0)
  * InceptionV3 batch sweep (128 / 256 / 512)
  * ResNet50 fused downsample shortcut (SPARKDL_RN_FUSED_SHORTCUT=1 vs 0)
  * MobileNetV2 fused inverted-residual tail (SPARKDL_MNV2_FUSED=1 vs 0)

Method: ``bench.measure_scan`` (steps-in-one-program); models build
fresh per run so the env knobs bind at build time.  Everything runs in
this one process; without an accelerator it fails, and every line is
stamped with the device that measured it.

Run: python tools/perf_experiments.py [xception|inception|resnet|mobilenet|batch]...
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def run(name, featurize, batch, steps, **env):
    old = {}
    for k, v in env.items():
        old[k] = os.environ.get(k)
        os.environ[k] = v
    try:
        fn, variables, (h, w) = bench._zoo_fn(name, featurize=featurize)
        ips = bench.measure_scan(fn, variables, h, w, batch, steps)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    print(json.dumps({"model": name, "batch": batch, "env": env,
                      "ips": round(ips, 1),
                      "device": bench.device_stamp()}), flush=True)
    return ips


def xception_ab(batch=128, steps=40):
    a = run("Xception", False, batch, steps, SPARKDL_XC_TILED="1")
    b = run("Xception", False, batch, steps, SPARKDL_XC_TILED="0")
    print(json.dumps({"experiment": "xception_tiled_entry",
                      "tiled": round(a, 1), "xla_entry": round(b, 1),
                      "delta_pct": round((a / b - 1) * 100, 1)}), flush=True)


def inception_ab(batch=128, steps=40):
    a = run("InceptionV3", True, batch, steps, SPARKDL_FUSED_HEADS="1")
    b = run("InceptionV3", True, batch, steps, SPARKDL_FUSED_HEADS="0")
    print(json.dumps({"experiment": "inception_fused_heads",
                      "fused": round(a, 1), "per_branch": round(b, 1),
                      "delta_pct": round((a / b - 1) * 100, 1)}), flush=True)


def resnet_ab(batch=128, steps=40):
    a = run("ResNet50", False, batch, steps, SPARKDL_RN_FUSED_SHORTCUT="1")
    b = run("ResNet50", False, batch, steps, SPARKDL_RN_FUSED_SHORTCUT="0")
    print(json.dumps({"experiment": "resnet_fused_shortcut",
                      "fused": round(a, 1), "per_conv": round(b, 1),
                      "delta_pct": round((a / b - 1) * 100, 1)}), flush=True)


def mobilenet_ab(batch=256, steps=40):
    a = run("MobileNetV2", False, batch, steps, SPARKDL_MNV2_FUSED="1")
    b = run("MobileNetV2", False, batch, steps, SPARKDL_MNV2_FUSED="0")
    print(json.dumps({"experiment": "mobilenet_fused_tail",
                      "fused": round(a, 1), "xla": round(b, 1),
                      "delta_pct": round((a / b - 1) * 100, 1)}), flush=True)


def inception_batch_sweep(steps=40):
    out = {}
    for batch in (128, 256, 512):
        out[batch] = round(run("InceptionV3", True, batch,
                               max(10, steps // (batch // 128))), 1)
    print(json.dumps({"experiment": "inception_batch_sweep", **{
        str(k): v for k, v in out.items()}}), flush=True)


if __name__ == "__main__":
    from sparkdl_tpu.parallel import compile_cache

    compile_cache.configure_default()
    bench.require_accelerator()
    wanted = sys.argv[1:] or ["xception", "inception", "batch"]
    if "xception" in wanted:
        xception_ab()
    if "inception" in wanted:
        inception_ab()
    if "resnet" in wanted:
        resnet_ab()
    if "mobilenet" in wanted:
        mobilenet_ab()
    if "batch" in wanted:
        inception_batch_sweep()
