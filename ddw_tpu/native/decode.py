"""ctypes bindings for the native JPEG decode pipeline (pipeline.cpp).

The loader/serving hot loop: JPEG -> RGB -> bilinear resize -> [-1, 1] f32,
single images or whole batches on a C++ thread pool (one GIL release per
batch). An individual image that fails to decode is flagged and re-decoded by
the caller through PIL — same dispatch on the training and serving sides, so
there is no train/serve preprocessing skew (SURVEY.md §7 step 7). A library
that cannot be built raises :class:`~ddw_tpu.native.build.NativeBuildError`;
nothing drops to PIL wholesale.
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

from ddw_tpu.native.build import LazyLibrary

_HERE = os.path.dirname(__file__)


def _configure(lib: ctypes.CDLL) -> None:
    lib.ddws_decode_one.restype = ctypes.c_int
    lib.ddws_decode_one.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float)]
    lib.ddws_decode_batch.restype = ctypes.c_long
    lib.ddws_decode_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_long), ctypes.c_long,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_ubyte)]


_library = LazyLibrary(
    src=os.path.join(_HERE, "pipeline.cpp"),
    lib=os.path.join(_HERE, "libddwpipeline.so"),
    extra_flags=("-ljpeg",),
    configure=_configure,
)


def native_available() -> bool:
    """True once the library is built and loaded; a build or load failure
    raises :class:`~ddw_tpu.native.build.NativeBuildError` instead."""
    _library.load()
    return True


def decode_one_native(content: bytes, height: int, width: int) -> np.ndarray | None:
    """Decode one JPEG to float32 [H, W, 3] in [-1, 1]; None if this image
    does not decode."""
    lib = _library.load()
    out = np.empty((height, width, 3), np.float32)
    rc = lib.ddws_decode_one(
        content, len(content), height, width,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return out if rc == 0 else None


def decode_batch_native(
    contents: list[bytes], height: int, width: int, threads: int = 4,
    out: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Decode a batch of JPEGs on the C++ thread pool.

    Returns ``(images [N, H, W, 3] f32, ok [N] bool)`` — failed slots are left
    uninitialized and flagged False (callers re-decode those via PIL).
    ``out`` reuses a caller buffer.
    """
    lib = _library.load()
    n = len(contents)
    if out is None:
        out = np.empty((n, height, width, 3), np.float32)
    else:
        # The kernel writes n*h*w*3 f32 through the raw pointer — a wrong
        # dtype/shape/layout here is silent memory corruption, not an error.
        if out.dtype != np.float32:
            raise ValueError(f"out must be float32, got {out.dtype}")
        if out.shape != (n, height, width, 3):
            raise ValueError(
                f"out shape {out.shape} != {(n, height, width, 3)}")
        if not out.flags.c_contiguous:
            raise ValueError("out must be C-contiguous")
        if not out.flags.writeable:
            raise ValueError("out must be writeable")
    ok = np.zeros((n,), np.uint8)
    if n == 0:
        return out, ok.astype(bool)
    offsets = np.zeros((n + 1,), np.int64)
    np.cumsum([len(c) for c in contents], out=offsets[1:])
    blob = b"".join(contents)
    lib.ddws_decode_batch(
        blob, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)), n,
        height, width, threads,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)))
    return out, ok.astype(bool)
