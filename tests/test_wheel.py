"""Wheel artifact proof (VERDICT r3 #4 / SURVEY C17).

``pip install -e .`` (what the dev loop uses) never exercises package-data,
so these tests build the real wheel, install it into a clean target, and
smoke-import from there — proving the artifact users get actually ships
the native source and the offline data dir and that the PIL fallback
engages without a build step.
"""

import os
import subprocess
import sys
import zipfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def wheel_path(tmp_path_factory):
    # build from a COPY of the checkout: setuptools leaves build/ and an
    # egg-info beside the sources it builds, and build/lib/sparkdl_tpu
    # is a second copy of the package that a stray PYTHONPATH imports
    import shutil

    d = tmp_path_factory.mktemp("wheel")
    src = d / "src"
    shutil.copytree(REPO, src, ignore=shutil.ignore_patterns(
        ".git", "build", "*.egg-info", "__pycache__", "_build",
        "artifacts", ".compile_cache", "chiprun_out", ".pytest_cache"))
    proc = subprocess.run(
        [sys.executable, "-m", "pip", "wheel", str(src), "--no-deps",
         "--no-build-isolation", "-w", str(d)],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    wheels = [f for f in os.listdir(d) if f.endswith(".whl")]
    assert len(wheels) == 1, wheels
    return str(d / wheels[0])


def test_wheel_ships_package_data(wheel_path):
    """The wheel must contain the lazy-build native source and the offline
    model-data dir — the two package-data claims of pyproject.toml."""
    names = zipfile.ZipFile(wheel_path).namelist()
    assert "sparkdl_tpu/native/sparkdl_native.cpp" in names
    assert "sparkdl_tpu/models/data/README.md" in names
    # and no test/bench stowaways
    assert not any(n.startswith(("tests/", "examples/")) for n in names)
    assert "bench.py" not in names


def test_wheel_installs_and_imports(wheel_path, tmp_path):
    """Install the wheel into a clean --target dir and import from THERE
    (repo not on the path): package imports, native source is present in
    the installed tree, and the image layer works via the PIL fallback."""
    target = tmp_path / "site"
    proc = subprocess.run(
        [sys.executable, "-m", "pip", "install", "--no-deps",
         "--target", str(target), wheel_path],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]

    smoke = r"""
import os, sys
import sparkdl_tpu
root = os.path.dirname(os.path.abspath(sparkdl_tpu.__file__))
assert root.startswith(sys.argv[1]), (root, sys.argv[1])
assert os.path.isfile(os.path.join(root, "native", "sparkdl_native.cpp"))
assert os.path.isfile(os.path.join(root, "models", "data", "README.md"))

# image layer end-to-end on the PIL path (no toolchain required)
import io
import numpy as np
from PIL import Image
from sparkdl_tpu.image import PIL_decode, imageArrayToStruct
from sparkdl_tpu.image.io import decodeResizeBatch
buf = io.BytesIO()
Image.fromarray(np.full((10, 12, 3), 55, np.uint8), "RGB").save(
    buf, format="JPEG")
batch, ok = decodeResizeBatch([buf.getvalue(), b"junk"], 8, 8)
assert batch.shape == (2, 8, 8, 3) and list(ok) == [True, False]

# native layer degrades gracefully (callable either way)
import sparkdl_tpu.native as native
assert native.native_available() in (True, False)

# the serving subsystem ships and imports without initializing jax
from sparkdl_tpu.serving import Server, from_transformer  # noqa: F401
print("WHEEL-SMOKE-OK")
"""
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH",)}
    env["PYTHONPATH"] = str(target)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", smoke, str(target)],
        capture_output=True, text=True, timeout=300,
        cwd=str(tmp_path), env=env)
    assert proc.returncode == 0, (proc.stdout[-1000:], proc.stderr[-2000:])
    assert "WHEEL-SMOKE-OK" in proc.stdout
