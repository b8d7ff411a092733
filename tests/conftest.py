"""Test harness.

Mirrors the reference's test strategy (SURVEY.md §4): everything runs
single-machine, with multi-chip behavior simulated — here via an 8-device
virtual CPU platform (``xla_force_host_platform_device_count``), the TPU
analog of the reference's `local[*]` SparkSession with multiple partitions.
"""

import os

# Must be set before the CPU backend initializes (XLA_FLAGS is read from the
# environment at client-creation time).
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Keras (used only as a parity oracle / legacy-import reader) on CPU TF.
os.environ.setdefault("KERAS_BACKEND", "tensorflow")
os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

# Tier-1 is a CPU suite: it must give the same answers on a machine
# that has an accelerator attached, so the platform is pinned in code as
# well as by the documented JAX_PLATFORMS=cpu.
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(seed=0)


@pytest.fixture(scope="session")
def fixture_images(tmp_path_factory, rng):
    """A handful of tiny real JPEG files — the reference tests use small
    image fixtures under python/tests/resources/images/; we synthesize ours
    (no bundled binaries) but they are real encoded JPEGs on disk."""
    from PIL import Image

    d = tmp_path_factory.mktemp("images")
    paths = []
    for i, size in enumerate([(32, 48), (64, 64), (50, 40)]):
        arr = (rng.random((size[1], size[0], 3)) * 255).astype("uint8")
        p = d / f"img_{i}.jpg"
        Image.fromarray(arr).save(p, quality=95)
        paths.append(str(p))
    # one non-image file to exercise decode-failure handling
    bad = d / "not_an_image.jpg"
    bad.write_bytes(b"this is not a jpeg")
    return {"dir": str(d), "paths": paths, "bad": str(bad)}
