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


class _TinyZooModule:
    """A zoo module's surface over a trivial function of the input."""

    def apply(self, variables, x, train=False, features=False):
        import jax.numpy as jnp

        m = jnp.mean(x, axis=(1, 2, 3))
        idx = jnp.arange(2048 if features else 1000, dtype=jnp.float32)
        return m[:, None] * 0.01 + idx[None, :] * 1e-4


@pytest.fixture()
def tiny_resnet(monkeypatch):
    """The zoo's ``ResNet50`` stages over a toy module: the spec of the
    real one, an engine of their own."""
    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.transformers import named_image as ni

    monkeypatch.setitem(ni._MODEL_CACHE, ("ResNet50", ""),
                        (_TinyZooModule(), {}))
    ni._ENGINE_CACHE.clear()
    yield get_model_spec("ResNet50")
    ni._ENGINE_CACHE.clear()
