"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, through the entry points a user calls, at
the full width of the zoo models, on ONE TPU chip:

* batch:  ``imageIO.readImages`` over a directory of generated JPEGs (a
  few batches plus one undecodable file) -> ``DeepImageFeaturizer`` for
  InceptionV3, Xception and ResNet50 at the transformer's default batch
  size, in the default dtype and under ``SPARKDL_ZOO_COMPUTE_DTYPE=
  bfloat16``; Xception's program must hold the Pallas kernels and agree
  with the XLA lowering of the same variables;
* online: ``serving.from_transformer(DeepImageFeaturizer(InceptionV3))``
  answers a few dozen requests of mixed arrival over at least two
  compiled buckets and agrees with the batch transform;
* fit:    the README's ``Pipeline([DeepImageFeaturizer,
  LogisticRegression]).fit`` (steps through ``fit_data_parallel``), then
  ``transform``; and ``ImageFileEstimator`` over a small flax CNN.

Weights are the specs' seeded ``init_variables`` and images are made
from the same seed: the machine has no network and no weight files.

``--chips 4`` runs INSTEAD the paths that exist only across chips
(ResNet50 on the default dp4 mesh and on dp2 x tp2 under the default
partition rules, data-parallel train steps), each against one device,
with placement asserted — and nothing else.

Contract with the driver: exits non-zero, printing no result, unless
``jax.devices()[0].platform`` is the TPU (and the device count is the
one asked for); exits non-zero if any phase raises or any check fails;
prints one JSON object per phase (bring-up observations — wall seconds
around compiling calls are NOT benchmark numbers) and, as the LAST
stdout line, ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
One process touches the chip; nothing is left running.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import tempfile
import time
import traceback

import numpy as np

#: the platform a run must find; tests steer it to run phases on the CPU
PLATFORM = "tpu"
SEED = 0

# -- tolerances, each with its reason --------------------------------------
#: Two programs that compute the same f32 math in differently shaped
#: pieces — a server bucket of 8 against a batch of 64, a weight shard
#: against the whole kernel, a quarter of a batch against all of it — keep
#: every contraction whole, so they are equal up to the order XLA's
#: shape-chosen emitters sum one element's products in: not bit for bit.
#: On the TPU that last-bit difference does not stay in the last bit: the
#: default matmul precision rounds each conv's f32 operands to bf16, and
#: an activation that differs in its last f32 bit can land on the other
#: side of that rounding (a 2^-8 relative step) in the next layer.  A few
#: such flips per layer, through 50-100 layers, measured 3.3e-4 (serving)
#: and 8.2e-4 (dp2 x tp2) of the feature scale on a v5e (my chip runs,
#: PR 21).  The bound is 2.4x the worst of those and has to stay BELOW
#: what running the whole model in bf16 costs — 3.6e-3 for ResNet50 in
#: the same run — or a path that silently computed in bf16 would pass.  A
#: wrong weight, a missing all-gather or a transposed image is O(1).
#: A server bucket (8/16/32 rows) vs the transform's batch (64 rows).
SERVING_VS_BATCH_RTOL = 2e-3
#: Sharded (dp2 x tp2, default partition rules: OUTPUT dims split, no
#: reduction crosses shards) or data-parallel (dp4) vs one device — the
#: words and the reasoning of tests/test_mesh_shard.py::
#: test_server_sharded_parity_dp2tp4, compounded through ResNet50's 53
#: convolutions.
SHARDED_VS_ONE_RTOL = 2e-3
#: Data-parallel training of a linear head: one matmul deep, so nothing
#: compounds.  All that differs from one device is that each step's
#: gradient is an all-reduce of four per-shard sums, i.e. one
#: reassociation of an f32 sum per step; measured 0.0 (weights) and 1e-7
#: (losses) on four v5e chips (my chip run, PR 21).  100x that.
TRAIN_DP_VS_ONE_RTOL = 1e-5
#: The kernel path STORES every separable conv's output in bf16 (the
#: kernels' storage dtype) where the XLA lowering keeps f32: a 2^-9
#: relative rounding per layer over 34 chained layers, on every element.
#: tests/test_ops_sepconv.py holds the same pair to 5% at model level; so
#: does this, relative to the feature scale (measured 0.4% on a v5e).
KERNEL_VS_XLA_RTOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """How much of everything; the defaults are the real run, tests pass
    toy ones."""
    models: tuple = ("InceptionV3", "Xception", "ResNet50")
    n_images: int = 150          # -> 64 + 64 + 22 at the default batch
    serve_model: str = "InceptionV3"
    serve_batch: int = 32        # buckets 8/16/32, the audited plan
    serve_requests: int = 40
    fit_epochs: int = 50         # LogisticRegression's default maxIter
    cnn_epochs: int = 3
    chips_batch: int = 64
    chips_train_rows: int = 256


class CheckFailed(AssertionError):
    """A phase ran but what came out is wrong."""


def check(cond, message: str) -> None:
    if not cond:
        raise CheckFailed(message)


def _rel_err(got, want) -> float:
    """max|got-want| relative to the scale of ``want``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _cross_entropy(prob, labels) -> float:
    """Mean negative log-probability of the true class."""
    prob, labels = np.asarray(prob, np.float64), np.asarray(labels)
    picked = prob[np.arange(len(labels)), labels]
    return float(-np.log(np.clip(picked, 1e-12, 1.0)).mean())


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def _env(name: str, value: str):
    old = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


# -- set-up: images and weights from the seed --------------------------------

def make_images(root: str, hw: int, n: int) -> str:
    """``n`` labelled JPEGs of ``hw`` x ``hw`` (class 0 reddish, class 1
    bluish, so a linear head on any reasonable features separates them)
    plus one file that is not an image; returns the directory."""
    from PIL import Image

    d = os.path.join(root, f"images_{hw}")
    os.makedirs(d)
    rng = np.random.default_rng(SEED + hw)
    for i in range(n):
        label = i % 2
        arr = rng.integers(0, 256, (hw, hw, 3)).astype(np.float32)
        arr[..., 0 if label == 0 else 2] *= 0.35
        Image.fromarray(arr.astype(np.uint8), "RGB").save(
            os.path.join(d, f"img_{i:04d}_c{label}.jpg"), quality=92)
    with open(os.path.join(d, "zz_not_an_image.jpg"), "wb") as fh:
        fh.write(b"this is not a jpeg")
    return d


def seed_zoo_weights(name: str) -> None:
    """Serve the spec's seeded ``init_variables`` for ``name`` in this
    process, where a Keras import (a weights file, or the network) would
    otherwise fill the zoo's weight cache."""
    import jax

    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.transformers.named_image import set_zoo_model

    spec = get_model_spec(name)
    set_zoo_model(name, spec.build(),
                  spec.init_variables(rng=jax.random.PRNGKey(SEED)))


def _engine_that_ran(name: str, dtype: str):
    """The engine ``DeepImageFeaturizer`` ran (name, dtype) on: the
    stage's own, process-wide — and its row counter must say that it has
    run, so that what is inspected IS what ran."""
    from sparkdl_tpu import DeepImageFeaturizer

    with _env("SPARKDL_ZOO_COMPUTE_DTYPE", dtype):
        eng = DeepImageFeaturizer(modelName=name).engine()
    check(eng.metrics.counters.get("engine.rows", 0) > 0,
          f"no {name}/{dtype} featurize engine had run")
    return eng


def _labels(df):
    """0/1 labels from the generated file names (``..._c<label>.jpg``)."""
    return [int(row["image"]["origin"][-5]) for row in df.collect()]


# -- phases (one chip) ---------------------------------------------------------

def phase_environment(expect_chips: int) -> dict:
    import jax
    import jaxlib

    from sparkdl_tpu.parallel.mesh import device_stamp

    stamp = device_stamp()
    check(stamp["platform"] == PLATFORM,
          f"JAX found platform {stamp['platform']!r}, not {PLATFORM!r}")
    check(stamp["count"] == expect_chips,
          f"expected {expect_chips} device(s), JAX found {stamp['count']}")
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = None
    return {"device": stamp, "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "python": sys.version.split()[0]}


def phase_compile_cache() -> dict:
    """For a server a cache that does not come up is a warning; here it is
    a failed phase."""
    from sparkdl_tpu.parallel import compile_cache

    st = compile_cache.configure_default()
    check(st is not None, "the persistent compile cache did not come up")
    return {"dir": st["dir"], "placed": st["placed"],
            "reused": st["reused"], "invalidated": st["invalidated"]}


def phase_decoder(image_dir: str) -> dict:
    """Which decoder this machine runs; the native core must have built
    (from source, keyed on its content) and must agree with PIL."""
    import sparkdl_tpu.native as native
    from sparkdl_tpu.image import io as image_io

    info = native.library_info()
    check(info["decoder"] == "native",
          "the native decode core did not build; PIL fallback in use")
    paths = sorted(os.listdir(image_dir))[:8]
    blobs = [open(os.path.join(image_dir, p), "rb").read() for p in paths]
    got, ok = image_io.decodeResizeBatch(blobs, 96, 96)
    check(bool(ok.all()), "native decode dropped a valid JPEG")
    ref = np.stack([image_io.resizeImage(image_io.PIL_decode(b), 96, 96)
                    [:, :, ::-1] for b in blobs])
    diff = float(np.abs(got.astype(np.int32) - ref.astype(np.int32)).mean())
    # tests/test_native.py's bound: libjpeg DCT prescale vs PIL bilinear
    check(diff < 8.0, f"native vs PIL decode mean abs diff {diff}")
    return {**info, "mean_abs_diff_vs_pil": round(diff, 3)}


def phase_featurize(name: str, dtype: str, image_dir: str,
                    reference=None):
    """``readImages`` -> ``DeepImageFeaturizer.transform`` at the default
    batch size; returns (observations, features of the valid rows)."""
    from sparkdl_tpu import DeepImageFeaturizer, readImages
    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.parallel.pipeline import pipeline_stage_summary

    spec = get_model_spec(name)
    df = readImages(image_dir)
    n_files = len(os.listdir(image_dir))
    stage = DeepImageFeaturizer(inputCol="image", outputCol="features",
                                modelName=name)
    with _env("SPARKDL_ZOO_COMPUTE_DTYPE", dtype):
        t0 = time.perf_counter()
        rows = stage.transform(df).collect()
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        rows2 = stage.transform(df).collect()
        steady_s = time.perf_counter() - t0
    check(len(rows) == n_files, f"{len(rows)} rows for {n_files} files")
    nulls = [i for i, r in enumerate(rows) if r["features"] is None]
    check(len(nulls) == 1 and rows[nulls[0]]["image"] is None,
          f"the undecodable file should be the one null row, got {nulls}")
    feats = np.asarray([r["features"] for r in rows
                        if r["features"] is not None], np.float32)
    check(feats.shape == (n_files - 1, spec.feature_size),
          f"features {feats.shape}, want (., {spec.feature_size})")
    check(bool(np.isfinite(feats).all()), "non-finite features")
    check(float(feats.std()) > 0, "constant features")
    again = np.asarray([r["features"] for r in rows2
                        if r["features"] is not None], np.float32)
    check(np.array_equal(feats, again),
          "the same program on the same images gave different features")
    eng = _engine_that_ran(spec.name, dtype)
    obs = {"model": spec.name, "dtype": dtype,
           "batch": eng.device_batch_size, "images": n_files - 1,
           "feature_width": int(feats.shape[1]),
           "first_call_s": round(first_s, 3),
           "steady_call_s": round(steady_s, 3),
           "peak_bytes_in_use": _peak_bytes(),
           "pipeline_stages": pipeline_stage_summary(eng.metrics)}
    if reference is not None:
        # observation, not a check: bf16 compute against the f32 run
        obs["rel_err_vs_float32"] = round(_rel_err(feats, reference), 5)
    return obs, feats


def phase_xception_kernel(image_dir: str, features) -> dict:
    """The program that just featurized with Xception holds the Pallas
    kernels (a silent reference-path fallback has none), and agrees with
    the XLA lowering of the SAME variables."""
    import jax

    from sparkdl_tpu import readImages
    from sparkdl_tpu.image.io import arrowStructsToBatch
    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.models.xception import Xception
    from sparkdl_tpu.parallel.engine import InferenceEngine
    from sparkdl_tpu.transformers import named_image

    spec = get_model_spec("Xception")
    h, w = spec.input_size
    eng = _engine_that_ran("Xception", "float32")
    calls = eng.compiled_text(jax.ShapeDtypeStruct(
        (eng.device_batch_size, h, w, 3), np.uint8)).count("tpu_custom_call")
    check(calls > 0, "Xception's compiled program holds no Pallas kernel: "
          "the reference path ran instead")
    with _env("SPARKDL_ZOO_COMPUTE_DTYPE", "float32"):
        _, variables, _ = named_image.zoo_serving_bundle("Xception",
                                                         featurize=True)
    xla = InferenceEngine(
        named_image.zoo_model_fn("Xception", featurize=True,
                                 module=Xception(fused_inference=False)),
        variables, device_batch_size=eng.device_batch_size)
    col = readImages(image_dir).table.column("image")
    batch, _ = arrowStructsToBatch(col, h, w, compact=True)
    rel = _rel_err(features, xla(batch))
    check(rel <= KERNEL_VS_XLA_RTOL,
          f"kernel vs XLA path differ by {rel} of the feature scale "
          f"(tolerance {KERNEL_VS_XLA_RTOL})")
    return {"model": "Xception", "tpu_custom_calls": calls,
            "rel_err_kernel_vs_xla": round(rel, 5),
            "tolerance": KERNEL_VS_XLA_RTOL}


def phase_serving(sizes: Sizes, image_dir: str, batch_features) -> dict:
    """``serving.from_transformer`` over the same weights: mixed arrival
    (a burst, then stragglers) so at least two buckets serve traffic."""
    from sparkdl_tpu import DeepImageFeaturizer, obs, readImages, serving

    check(batch_features is not None,
          f"no batch features of {sizes.serve_model} to serve against")
    structs = [r["image"] for r in readImages(image_dir).collect()
               if r["image"] is not None][:sizes.serve_requests]
    want = np.asarray(batch_features)[:len(structs)]
    stage = DeepImageFeaturizer(inputCol="image", outputCol="features",
                                modelName=sizes.serve_model,
                                batchSize=sizes.serve_batch)
    tracer = obs.configure(enabled=True)
    try:
        with serving.from_transformer(stage, max_wait_ms=20.0) as srv:
            t0 = time.perf_counter()
            srv.warmup(structs[0])
            warm_s = time.perf_counter() - t0
            tracer.clear()
            t0 = time.perf_counter()
            burst = len(structs) - 6
            futs = [srv.submit(s) for s in structs[:burst]]
            for s in structs[burst:burst + 3]:   # stragglers, one by one
                time.sleep(0.08)
                futs.append(srv.submit(s))
            time.sleep(0.08)
            futs += [srv.submit(s) for s in structs[burst + 3:]]
            got = np.asarray([f.result(timeout=300) for f in futs],
                             np.float32)
            serve_s = time.perf_counter() - t0
            varz = srv.varz()
        buckets = sorted({s["attrs"]["bucket"] for s in tracer.snapshot()
                          if s["name"] == "serving.microbatch"
                          and "bucket" in s.get("attrs", {})})
    finally:
        obs.configure_from_env()
    check(got.shape == want.shape, f"served {got.shape}, want {want.shape}")
    check(len(buckets) >= 2,
          f"traffic reached only bucket(s) {buckets} of "
          f"{varz['server']['bucket_sizes']}")
    rel = _rel_err(got, want)
    check(rel <= SERVING_VS_BATCH_RTOL,
          f"served rows differ from the batch transform by {rel} of the "
          f"feature scale (tolerance {SERVING_VS_BATCH_RTOL})")
    return {"model": sizes.serve_model, "requests": len(futs),
            "bucket_plan": varz["server"]["bucket_sizes"],
            "buckets_served": buckets,
            "batches": int(varz["counters"].get("serving.batches", 0)),
            "warmup_all_buckets_s": round(warm_s, 3),
            "serve_s": round(serve_s, 3),
            "rel_err_vs_batch": round(rel, 6),
            "tolerance": SERVING_VS_BATCH_RTOL,
            "peak_bytes_in_use": _peak_bytes()}


def phase_fit_pipeline(sizes: Sizes, image_dir: str) -> dict:
    """The README's transfer-learning pipeline, fit and transform."""
    import pyarrow as pa

    from sparkdl_tpu import DeepImageFeaturizer, readImages
    from sparkdl_tpu.estimators import LogisticRegression
    from sparkdl_tpu.transformers import Pipeline

    train = readImages(image_dir).dropna("image")
    train = train.withColumn(
        "label", pa.array(_labels(train), type=pa.int64()))
    n_rows = len(train)
    pipe = Pipeline(stages=[
        DeepImageFeaturizer(inputCol="image", outputCol="features",
                            modelName=sizes.serve_model),
        LogisticRegression(featuresCol="features", labelCol="label",
                           maxIter=sizes.fit_epochs),
    ])
    t0 = time.perf_counter()
    model = pipe.fit(train)
    fit_s = time.perf_counter() - t0
    rows = model.transform(train).collect()
    y = np.asarray([r["label"] for r in rows])
    prob = np.asarray([r["probability"] for r in rows], np.float64)
    pred = np.asarray([r["prediction"] for r in rows])
    check(prob.shape == (n_rows, 2) and pred.shape == (n_rows,),
          f"predictions {pred.shape} / probabilities {prob.shape}")
    check(bool(np.isfinite(prob).all()), "non-finite probabilities")
    loss = _cross_entropy(prob, y)
    # the head starts at ~zero weights: its loss there is ln(2)
    check(loss < math.log(2.0),
          f"training loss {loss} did not fall below ln 2")
    return {"estimator": "Pipeline[DeepImageFeaturizer, "
                         "LogisticRegression]", "rows": n_rows,
            "epochs": sizes.fit_epochs, "fit_s": round(fit_s, 3),
            "loss_initial": round(math.log(2.0), 4),
            "loss_final": round(loss, 4),
            "train_accuracy": round(float((pred == y).mean()), 4)}


def phase_fit_cnn(sizes: Sizes, image_dir: str) -> dict:
    """``ImageFileEstimator`` over a small flax CNN: a few epochs through
    the same data-parallel train step, from file paths."""
    import jax
    from flax import linen as nn

    from sparkdl_tpu.estimators import ImageFileEstimator
    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.graph.function import ModelFunction

    class SmallCNN(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Conv(8, (3, 3), strides=(2, 2))(x))
            x = nn.relu(nn.Conv(16, (3, 3), strides=(2, 2))(x))
            return nn.softmax(nn.Dense(2)(x.mean(axis=(1, 2))))

    def loader(uri):
        from PIL import Image

        img = Image.open(uri).convert("RGB").resize((32, 32))
        return np.asarray(img, np.float32) / 255.0

    paths = sorted(os.path.join(image_dir, p) for p in os.listdir(image_dir)
                   if "_c" in p)
    y = np.asarray([int(p[-5]) for p in paths])
    df = DataFrame({"uri": paths,
                    "label": [[1.0, 0.0] if v == 0 else [0.0, 1.0]
                              for v in y]})
    module = SmallCNN()
    x = np.stack([loader(p) for p in paths])
    variables = jax.tree_util.tree_map(
        np.asarray, jax.jit(module.init, donate_argnums=())(
            jax.random.PRNGKey(SEED), x[:1]))
    mf = ModelFunction.from_flax(module, variables)

    before = _cross_entropy(
        jax.jit(mf.fn, donate_argnums=())(variables, x), y)
    est = ImageFileEstimator(
        inputCol="uri", outputCol="preds", labelCol="label",
        modelFunction=mf, imageLoader=loader, optimizer="adam",
        loss="categorical_crossentropy",
        fitParams={"epochs": sizes.cnn_epochs, "seed": SEED}, batchSize=32)
    t0 = time.perf_counter()
    fitted = est.fit(df)
    fit_s = time.perf_counter() - t0
    rows = fitted.transform(df).collect()
    check(len(rows) == len(paths) and len(rows[0]["preds"]) == 2,
          "ImageFileEstimator predictions have the wrong shape")
    after = _cross_entropy([r["preds"] for r in rows], y)
    check(math.isfinite(after) and after < before,
          f"CNN training loss went {before} -> {after}")
    return {"estimator": "ImageFileEstimator[SmallCNN]", "rows": len(paths),
            "epochs": sizes.cnn_epochs, "fit_s": round(fit_s, 3),
            "loss_initial": round(before, 4), "loss_final": round(after, 4)}


# -- the four-chip phase ---------------------------------------------------------

def _distinct_devices(arr, want: int, shard_shape, what: str) -> None:
    shards = arr.addressable_shards
    devices = {s.device.id for s in shards}
    check(len(shards) == want and len(devices) == want,
          f"{what}: {len(shards)} shard(s) on {len(devices)} distinct "
          f"device(s), want {want}")
    shapes = {tuple(s.data.shape) for s in shards}
    check(shapes == {tuple(shard_shape)},
          f"{what}: shard shapes {shapes}, want {tuple(shard_shape)}")


def _bytes_in_use():
    import jax

    return [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()]


def _memory_moved(before, after, at_least: float, what: str) -> list:
    """Every device's live bytes grew by ``at_least`` — code that never
    saw more than one chip may put everything on the first."""
    if any(b is None for b in after):
        check(PLATFORM != "tpu", "the TPU reports no memory_stats()")
        return []
    grew = [a - b for a, b in zip(after, before)]
    check(all(g >= at_least for g in grew),
          f"{what}: per-device growth {grew} bytes, want >= "
          f"{int(at_least)} on EVERY device")
    return grew


def phase_chips_featurize(sizes: Sizes, model: str = "ResNet50") -> dict:
    """``model`` on the default mesh (dp over every chip) and on dp2 x tp2
    under the default partition rules, each against one device."""
    import jax

    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import InferenceEngine
    from sparkdl_tpu.transformers import named_image

    n = len(jax.devices())
    spec = get_model_spec(model)
    h, w = spec.input_size
    # the zoo's own resolution: seeded weights, fn through zoo_model_fn
    with _env("SPARKDL_ZOO_COMPUTE_DTYPE", "float32"):
        fn, variables, _ = named_image.zoo_serving_bundle(spec.name,
                                                          featurize=True)
    rng = np.random.default_rng(SEED)
    b = sizes.chips_batch
    x = rng.integers(0, 256, (b, h, w, 3)).astype(np.uint8)
    param_bytes = sum(int(np.asarray(leaf).nbytes)
                      for leaf in jax.tree_util.tree_leaves(variables))
    out = {"model": spec.name, "batch": b, "param_bytes": param_bytes}

    def kernels(eng):
        flat = jax.tree_util.tree_flatten_with_path(eng.variables)[0]
        return [(mesh_lib.param_path_str(p), leaf) for p, leaf in flat
                if mesh_lib.param_path_str(p).endswith("kernel")]

    def placed_run(label, eng, leaf_name, leaf, leaf_shard, out_rows,
                   before, at_least):
        """Assert where ``eng`` put one weight, its output and its bytes;
        returns (host features, per-device byte growth)."""
        _distinct_devices(leaf, n, leaf_shard, f"{label} weight {leaf_name}")
        _distinct_devices(eng.run_padded(x), n,
                          (out_rows, spec.feature_size), f"{label} output")
        got = np.asarray(eng(x))
        return got, _memory_moved(before, _bytes_in_use(), at_least,
                                  f"{label} weights")

    # default mesh first, on a fresh process: every device starts empty
    before = _bytes_in_use()
    dp = InferenceEngine(fn, variables, device_batch_size=b)
    check(dict(dp.mesh.shape) == {"data": n, "model": 1},
          f"default mesh is {dict(dp.mesh.shape)}, want data={n}")
    name, leaf = kernels(dp)[0]
    got_dp, grew = placed_run(f"dp{n}", dp, name, leaf, leaf.shape, b // n,
                              before, 0.9 * param_bytes)
    out[f"dp{n}"] = {"mesh": dict(dp.mesh.shape), "weights_on": n,
                     "output_shard": [b // n, spec.feature_size],
                     "bytes_grew_per_device": grew}

    before = _bytes_in_use()
    tp = InferenceEngine(fn, variables,
                         mesh=mesh_lib.get_mesh(model_parallel=2),
                         device_batch_size=b,
                         partition_rules=mesh_lib.default_partition_rules)
    info = tp.sharding_info()
    check(info["sharded"] and info["sharded_leaves"] > 0,
          "the default partition rules sharded nothing on dp x tp2")
    split = [(k, v) for k, v in kernels(tp)
             if tuple(v.sharding.spec)[-1:] == (mesh_lib.MODEL_AXIS,)]
    check(len(split) == info["sharded_leaves"],
          f"{len(split)} kernels carry the model axis, sharding_info says "
          f"{info['sharded_leaves']}")
    name, leaf = max(split, key=lambda kv: kv[1].size)
    half = leaf.shape[:-1] + (leaf.shape[-1] // 2,)
    got_tp, grew = placed_run(f"dp{n // 2}xtp2", tp, name, leaf, half,
                              b // (n // 2), before,
                              0.9 * info["param_bytes_per_chip"])
    out[f"dp{n // 2}xtp2"] = {
        "mesh": dict(tp.mesh.shape), "sharded_leaves": info["sharded_leaves"],
        "param_bytes_per_chip": info["param_bytes_per_chip"],
        "largest_sharded_kernel": name, "kernel_shard": list(half),
        "bytes_grew_per_device": grew}

    one = InferenceEngine(fn, variables,
                          mesh=mesh_lib.get_mesh(num_devices=1),
                          device_batch_size=b)
    want = np.asarray(one(x))
    check(bool(np.isfinite(want).all()) and float(want.std()) > 0,
          "single-device features are not finite or constant")
    for label, got in ((f"dp{n}", got_dp), (f"dp{n // 2}xtp2", got_tp)):
        rel = _rel_err(got, want)
        check(rel <= SHARDED_VS_ONE_RTOL,
              f"{label} differs from one device by {rel} of the feature "
              f"scale (tolerance {SHARDED_VS_ONE_RTOL}: equal up to "
              f"summation order, not bit for bit)")
        out[label]["rel_err_vs_one_device"] = round(rel, 7)
    out["tolerance"] = SHARDED_VS_ONE_RTOL
    out["peak_bytes_in_use_device0"] = _peak_bytes()
    return out


def phase_chips_xception_program() -> dict:
    """Which Xception program a multi-chip host gets: auto mode turns the
    Pallas kernels off there (Mosaic will not partition a kernel over the
    engine's mesh), so the answer should be the XLA lowering — printed,
    and compiled to prove it, not run."""
    import jax

    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import build_dispatch_jit
    from sparkdl_tpu.transformers.named_image import zoo_model_fn

    spec = get_model_spec("Xception")
    h, w = spec.input_size
    mesh = mesh_lib.get_mesh()
    b = 16 * mesh.shape[mesh_lib.DATA_AXIS]
    compiled = build_dispatch_jit(
        zoo_model_fn("Xception", featurize=True), mesh,
        donate_batch=False).lower(
            spec.abstract_variables(),
            jax.ShapeDtypeStruct((b, h, w, 3), np.uint8)).compile()
    calls = compiled.as_text().count("tpu_custom_call")
    return {"model": "Xception", "mesh": dict(mesh.shape), "batch": b,
            "tpu_custom_calls": calls,
            "program": "pallas kernels" if calls else "xla lowering"}


def phase_chips_train(sizes: Sizes) -> dict:
    """Data-parallel train steps (the estimator's linear head through
    ``make_train_step``/``fit_data_parallel``) on every chip against one."""
    import jax
    import optax

    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.train import fit_data_parallel, make_train_step

    n = len(jax.devices())
    rows, dim, classes, batch = sizes.chips_train_rows, 2048, 10, 64
    rng = np.random.default_rng(SEED)
    x = rng.normal(size=(rows, dim)).astype(np.float32)
    y = rng.integers(0, classes, rows).astype(np.int32)
    params = {"w": rng.normal(0, 0.01, (dim, classes)).astype(np.float32),
              "b": np.zeros((classes,), np.float32)}

    def predict(p, xb):
        return xb @ p["w"] + p["b"]

    opt = optax.sgd(0.05)
    step = make_train_step(predict, "sparse_categorical_crossentropy", opt,
                           cache=False)
    p_dev, s_dev = step.put_state(params, opt.init(params))
    _distinct_devices(p_dev["w"], n, (dim, classes), "train params w")
    xb, yb = step.put_batch(x[:batch], y[:batch])
    _distinct_devices(xb, n, (batch // n, dim), "train batch x")
    _, _, loss = step(p_dev, s_dev, xb, yb)
    check(math.isfinite(float(loss)), f"non-finite step loss {loss}")

    def fit(mesh):
        return fit_data_parallel(
            predict, params, x, y, optimizer=opt,
            loss="sparse_categorical_crossentropy", batch_size=batch,
            epochs=3, seed=SEED, mesh=mesh)

    p_all, l_all = fit(None)
    p_one, l_one = fit(mesh_lib.get_mesh(num_devices=1))
    check(l_all[-1] < l_all[0], f"loss did not fall: {l_all}")
    rel_w = _rel_err(p_all["w"], p_one["w"])
    rel_l = _rel_err(l_all, l_one)
    check(max(rel_w, rel_l) <= TRAIN_DP_VS_ONE_RTOL,
          f"dp{n} training differs from one device: weights {rel_w}, "
          f"losses {rel_l} (tolerance {TRAIN_DP_VS_ONE_RTOL})")
    return {"mesh": {"data": n, "model": 1},
            "steps": 3 * (rows // batch), "batch": batch,
            "batch_shard": [batch // n, dim],
            "losses": [round(v, 5) for v in l_all],
            "rel_err_weights_vs_one_device": round(rel_w, 7),
            "rel_err_losses_vs_one_device": round(rel_l, 7),
            "tolerance": TRAIN_DP_VS_ONE_RTOL}


# -- driver ----------------------------------------------------------------------

class Run:
    """Prints one JSON line per phase and remembers whether all passed."""

    def __init__(self):
        self.ok = True

    def phase(self, name: str, fn, *args, **kwargs):
        """Run one phase; its failure is printed, fails the run, and is
        NOT raised further, so later phases still report."""
        from sparkdl_tpu.parallel import compile_cache

        t0 = time.perf_counter()
        cache0 = compile_cache.stats()
        try:
            result = fn(*args, **kwargs)
        # graftlint: allow=SDL003 reason=phase boundary: the failure is printed as this phase's line with its traceback on stderr and fails the run's exit code; the remaining phases still report
        except Exception as e:  # noqa: BLE001
            self.ok = False
            traceback.print_exception(type(e), e, e.__traceback__,
                                      file=sys.stderr)
            print(json.dumps({"phase": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:600]}),
                  flush=True)
            return None
        obs, value = (result if isinstance(result, tuple)
                      else (result, None))
        cache1 = compile_cache.stats()
        print(json.dumps({
            "phase": name, "ok": True,
            "seconds": round(time.perf_counter() - t0, 3), **obs,
            "compile_cache": {k: cache1[k] - cache0[k] for k in cache1}}),
            flush=True)
        return value


def run_one_chip(run: Run, sizes: Sizes, root: str) -> None:
    dirs = {}
    from sparkdl_tpu.models import get_model_spec

    for name in sizes.models:
        hw = get_model_spec(name).input_size[0]
        if hw not in dirs:
            dirs[hw] = make_images(root, hw, sizes.n_images)
        seed_zoo_weights(name)
    first_dir = next(iter(dirs.values()))
    run.phase("decoder", phase_decoder, first_dir)
    features = {}
    for name in sizes.models:
        d = dirs[get_model_spec(name).input_size[0]]
        f32 = run.phase(f"featurize/{name}/float32", phase_featurize,
                        name, "float32", d)
        features[name] = f32
        if name == "Xception" and f32 is not None:
            run.phase("xception_kernel", phase_xception_kernel, d, f32)
        run.phase(f"featurize/{name}/bfloat16", phase_featurize,
                  name, "bfloat16", d, reference=f32)
    serve_dir = dirs[get_model_spec(sizes.serve_model).input_size[0]]
    run.phase("serving", phase_serving, sizes, serve_dir,
              features.get(sizes.serve_model))
    run.phase("fit/pipeline", phase_fit_pipeline, sizes, serve_dir)
    run.phase("fit/image_file_estimator", phase_fit_cnn, sizes, serve_dir)


def run_four_chips(run: Run, sizes: Sizes) -> None:
    seed_zoo_weights("ResNet50")
    run.phase("chips/featurize", phase_chips_featurize, sizes)
    run.phase("chips/xception_program", phase_chips_xception_program)
    run.phase("chips/train", phase_chips_train, sizes)


def main(argv=None, sizes: Sizes = Sizes()) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the paths that exist across chips "
                         "(needs a four-chip host)")
    args = ap.parse_args(argv)
    # the program first: alone in a directory this fails at once
    from sparkdl_tpu.parallel import compile_cache

    run = Run()
    try:
        env = phase_environment(args.chips)
    except CheckFailed as e:
        # no accelerator (or the wrong count): no result line at all
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"phase": "environment", "ok": True, **env}),
          flush=True)
    run.phase("compile_cache", phase_compile_cache)
    if args.chips == 4:
        run_four_chips(run, sizes)
    else:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
            run_one_chip(run, sizes, root)
    print(json.dumps({"phase": "summary", "ok": run.ok,
                      "compile_cache": {**compile_cache.stats(),
                                        "dir": (compile_cache.state()
                                                or {}).get("dir")}}),
          flush=True)
    print(json.dumps({"ok": run.ok, "device": env["device"]}), flush=True)
    return 0 if run.ok else 1


if __name__ == "__main__":
    sys.exit(main())
