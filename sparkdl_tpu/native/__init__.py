"""Native host-IO core: build + ctypes binding.

The reference had no in-repo native code — all native execution lived in
external engines (SURVEY.md §2 "Native components: NONE in-repo").  The TPU
build keeps the *compute* path in XLA but owns its host runtime: this module
compiles ``sparkdl_native.cpp`` (threaded fused JPEG/PNG decode+resize) on
first use with the system toolchain and binds it via ctypes (no pybind11 in
the image).  Everything degrades to the PIL path if the toolchain or
libjpeg/libpng are unavailable — the framework never hard-requires the
native core; :func:`library_info` says which one a process got.

The built library is keyed on the CONTENT of the source: its file name
carries the source's sha256, so a library built from another revision of
``sparkdl_native.cpp`` is never loaded, whatever the files' timestamps
say (a copied tree does not preserve them).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import List, Optional, Sequence, Tuple

import numpy as np

from sparkdl_tpu.analysis.lockcheck import named_lock
from sparkdl_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "sparkdl_native.cpp")
_LIB_DIR = os.path.join(os.path.dirname(__file__), "_build")

_lock = named_lock("native.load")
_lib = None
_lib_info: Optional[dict] = None   # what library_info() answers once loaded
_load_attempted = False


def _source_digest() -> str:
    with open(_SRC, "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()


def _lib_path_for(digest: str) -> str:
    return os.path.join(_LIB_DIR, f"libsparkdl_native-{digest[:16]}.so")


def _build(lib_path: str) -> bool:
    os.makedirs(_LIB_DIR, exist_ok=True)
    # build beside the target and rename: a concurrent process never
    # loads a half-written library
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
        _SRC, "-ljpeg", "-lpng", "-o", tmp,
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.warning("native build failed to run (%s); using PIL path", e)
        return False
    if proc.returncode != 0:
        logger.warning("native build failed; using PIL path:\n%s",
                       proc.stderr[-2000:])
        return False
    os.replace(tmp, lib_path)
    return True


def _load():
    global _lib, _lib_info, _load_attempted
    with _lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        if os.environ.get("SPARKDL_TPU_DISABLE_NATIVE"):
            logger.info("native IO disabled by SPARKDL_TPU_DISABLE_NATIVE")
            return None
        digest = _source_digest()
        lib_path = _lib_path_for(digest)
        if not os.path.exists(lib_path) and not _build(lib_path):
            return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            logger.warning("native library load failed (%s); using PIL path",
                           e)
            return None
        lib.sdl_decode_resize_batch.restype = ctypes.c_int
        lib.sdl_decode_resize_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
        ]
        lib.sdl_resize_batch.restype = None
        lib.sdl_resize_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
        ]
        _lib = lib
        _lib_info = {"decoder": "native", "path": lib_path,
                     "source_sha256": digest}
        logger.info("native IO core loaded (%s)", lib_path)
        return _lib


def native_available() -> bool:
    return _load() is not None


def library_info() -> dict:
    """Which decoder this process runs: ``{"decoder": "native", "path",
    "source_sha256"}`` once the core loaded (building it if need be),
    ``{"decoder": "pil"}`` when it could not."""
    if _load() is None:
        return {"decoder": "pil"}
    return dict(_lib_info)


def _default_threads() -> int:
    return min(16, os.cpu_count() or 4)


def decode_resize_batch(blobs: Sequence[bytes], height: int, width: int,
                        num_threads: Optional[int] = None
                        ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Fused decode(JPEG/PNG)+resize of encoded images into a [N,h,w,3]
    uint8 RGB batch + boolean ok-mask.  Returns None when the native core is
    unavailable (caller falls back to PIL)."""
    lib = _load()
    if lib is None:
        return None
    n = len(blobs)
    out = np.zeros((n, height, width, 3), dtype=np.uint8)
    status = np.zeros(n, dtype=np.uint8)
    if n == 0:
        return out, status.astype(bool)
    # Keep byte objects alive + build pointer arrays.
    buffers = [bytes(b) for b in blobs]
    ptrs = (ctypes.c_char_p * n)(*buffers)
    sizes = (ctypes.c_size_t * n)(*[len(b) for b in buffers])
    lib.sdl_decode_resize_batch(
        ptrs, sizes, n, height, width,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        status.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        num_threads or _default_threads())
    return out, status.astype(bool)


def resize_batch_rgb(images: Sequence[np.ndarray], height: int, width: int,
                     num_threads: Optional[int] = None
                     ) -> Optional[np.ndarray]:
    """Resize a list of [h,w,3] uint8 RGB arrays into one [N,h,w,3] batch.
    Returns None when the native core is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n = len(images)
    out = np.zeros((n, height, width, 3), dtype=np.uint8)
    if n == 0:
        return out
    contiguous = [np.ascontiguousarray(im, dtype=np.uint8) for im in images]
    for im in contiguous:
        if im.ndim != 3 or im.shape[2] != 3:
            raise ValueError(f"resize_batch_rgb needs [h,w,3] uint8 arrays, "
                             f"got {im.shape}")
    ptrs = (ctypes.c_char_p * n)(
        *[im.ctypes.data_as(ctypes.c_char_p) for im in contiguous])
    hs = (ctypes.c_int * n)(*[im.shape[0] for im in contiguous])
    ws = (ctypes.c_int * n)(*[im.shape[1] for im in contiguous])
    lib.sdl_resize_batch(
        ptrs, hs, ws, n, height, width,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        num_threads or _default_threads())
    return out
