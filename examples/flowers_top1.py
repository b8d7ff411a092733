"""Transfer-learning top-1 on a flowers-style dataset (BASELINE.md config #1).

The reference's README recipe — DeepImageFeaturizer(InceptionV3) + logistic
regression on tf_flowers — reproduced end-to-end.  Given a dataset laid out
as ``<root>/<class_name>/*.jpg`` (the tf_flowers archive layout), this
script featurizes every image on the TPU, fits the classifier head, and
prints one JSON line with held-out top-1 accuracy.

Usage:
    python examples/flowers_top1.py /data/flower_photos \
        [--model InceptionV3] [--train-ratio 0.8] [--batch-size 128] \
        [--max-per-class N] [--seed 0]

Real pretrained weights: set ``SPARKDL_WEIGHTS_DIR`` to a directory holding
``inception_v3.weights.h5`` (or ``.h5``/``.keras`` full models) — the
air-gapped weight contract (sparkdl_tpu/models/__init__.py).  Without it the
script falls back to the Keras download cache, and failing that to random
init (reported in the output; random-weight top-1 is only a smoke signal).

Output:
    {"top1": 0.93, "n_train": 2936, "n_test": 734, "classes": 5,
     "model": "InceptionV3", "weights_source": "...", "seconds": ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def gather_files(root: str, max_per_class: int | None):
    classes = sorted(
        d for d in os.listdir(root)
        if os.path.isdir(os.path.join(root, d)) and not d.startswith("."))
    if not classes:
        raise SystemExit(f"No class subdirectories under {root}")
    files, labels = [], []
    for ci, cls in enumerate(classes):
        cdir = os.path.join(root, cls)
        names = sorted(
            f for f in os.listdir(cdir)
            if f.lower().endswith((".jpg", ".jpeg", ".png")))
        if max_per_class:
            names = names[:max_per_class]
        for f in names:
            files.append(os.path.join(cdir, f))
            labels.append(ci)
    return files, np.asarray(labels), classes


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("root", help="dataset root: <root>/<class>/*.jpg")
    ap.add_argument("--model", default="InceptionV3")
    ap.add_argument("--train-ratio", type=float, default=0.8)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--max-per-class", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from sparkdl_tpu.estimators import LogisticRegression
    from sparkdl_tpu.frame import DataFrame
    from sparkdl_tpu.image.io import filesToModelBatch
    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.parallel.engine import InferenceEngine

    # perf_counter, not time.time(): "seconds" is an elapsed-time
    # measurement and wall clock can step under NTP slew (SDL006)
    t0 = time.perf_counter()
    files, labels, classes = gather_files(args.root, args.max_per_class)
    spec = get_model_spec(args.model)
    h, w = spec.input_size

    wdir = os.environ.get("SPARKDL_WEIGHTS_DIR")
    weights_source = (f"SPARKDL_WEIGHTS_DIR={wdir}" if wdir
                      else "keras-cache (random fallback if absent)")

    # Featurize everything: streaming decode -> jit featurize on the mesh.
    from sparkdl_tpu.models import load_model

    import jax.numpy as jnp

    module, variables = load_model(args.model)
    pre = spec.preprocess

    def fn(v, x):
        xf = pre(x).astype(jnp.bfloat16)
        return module.apply(v, xf, train=False, features=True
                            ).astype(jnp.float32)

    eng = InferenceEngine(fn, variables, device_batch_size=args.batch_size,
                          compute_dtype=jnp.bfloat16)

    def chunks():
        for off in range(0, len(files), eng.device_batch_size):
            batch, ok = filesToModelBatch(
                files[off:off + eng.device_batch_size], h, w)
            if not ok.all():
                bad = [files[off + i] for i in np.nonzero(~ok)[0]]
                print(f"warning: {len(bad)} undecodable files (first: "
                      f"{bad[0]})", file=sys.stderr)
            yield batch

    feats = np.concatenate(list(eng.map_batches(chunks())), axis=0)

    # Split and fit the head (the reference used Spark ML LogisticRegression
    # on the driver; ours trains data-parallel on the mesh).
    rng = np.random.default_rng(args.seed)
    order = rng.permutation(len(files))
    cut = int(len(files) * args.train_ratio)
    tr, te = order[:cut], order[cut:]
    train_df = DataFrame({"features": [feats[i].tolist() for i in tr],
                          "label": labels[tr].tolist()})
    test_df = DataFrame({"features": [feats[i].tolist() for i in te],
                         "label": labels[te].tolist()})
    lr = LogisticRegression(featuresCol="features", labelCol="label",
                            maxIter=100, learningRate=0.05, batchSize=256,
                            seed=args.seed)
    model = lr.fit(train_df)
    rows = model.transform(test_df).collect()
    y = np.asarray([r["label"] for r in rows])
    p = np.asarray([r["prediction"] for r in rows])
    print(json.dumps({
        "top1": round(float((y == p).mean()), 4),
        "n_train": int(len(tr)), "n_test": int(len(te)),
        "classes": len(classes), "model": args.model,
        "weights_source": weights_source,
        "seconds": round(time.perf_counter() - t0, 1),
    }))


if __name__ == "__main__":
    main()
