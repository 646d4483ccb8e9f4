"""ctypes bindings for the C++ shard codec (see codec.cpp for the role).

The shared library builds lazily with g++ on first use (toolchain is part of the
environment contract; pybind11 is not, hence the plain C ABI + ctypes). A build
or load failure raises :class:`~ddw_tpu.native.build.NativeBuildError` with the
compiler's message; the pure-Python codec in ``data/store.py`` is selected only
by ``DDW_NATIVE_CODEC=0``, never found by the program on its own.

Measured reality (kept honest per SURVEY.md §7 hard-part 3, "measure before
writing C++"): at realistic record sizes (3KB+) both codecs are memory-bound on
the content copy — native framing is ~parity, not a win; the loader's actual
bottleneck is JPEG decode (already C via PIL). The native path stays as the
foundation for a future zero-copy/mmap decode pipeline and as the in-tree native
storage layer the reference gets from Parquet C++.
"""

from __future__ import annotations

import ctypes
import os

from ddw_tpu.data.store import Record
from ddw_tpu.native.build import LazyLibrary

_HERE = os.path.dirname(__file__)


class _RecordIndex(ctypes.Structure):
    _fields_ = [
        ("path_off", ctypes.c_int64), ("path_len", ctypes.c_int64),
        ("content_off", ctypes.c_int64), ("content_len", ctypes.c_int64),
        ("label_off", ctypes.c_int64), ("label_len", ctypes.c_int64),
        ("label_idx", ctypes.c_int32), ("_pad", ctypes.c_int32),
    ]


def _configure(lib: ctypes.CDLL) -> None:
    lib.ddws_index_shard.restype = ctypes.c_int64
    lib.ddws_index_shard.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(_RecordIndex), ctypes.c_int64]
    lib.ddws_count_records.restype = ctypes.c_int64
    lib.ddws_count_records.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.ddws_validate.restype = ctypes.c_int64
    lib.ddws_validate.argtypes = [ctypes.c_char_p, ctypes.c_int64]


_library = LazyLibrary(
    src=os.path.join(_HERE, "codec.cpp"),
    lib=os.path.join(_HERE, "libddwcodec.so"),
    configure=_configure,
)


def native_available() -> bool:
    """True once the library is built and loaded; a build or load failure
    raises :class:`~ddw_tpu.native.build.NativeBuildError` instead."""
    _library.load()
    return True


def _index(path: str):
    lib = _library.load()
    with open(path, "rb") as f:
        buf = f.read()
    n = lib.ddws_count_records(buf, len(buf))
    if n < 0:
        raise RuntimeError(f"{path}: native codec header error {n}")
    # Header count is untrusted until the framing walk validates it: a record is
    # at least 16 bytes (3 length prefixes + label_idx), so bound the allocation.
    if n > (len(buf) - 12) // 16:
        raise RuntimeError(f"{path}: native codec header error (implausible count {n})")
    idx = (_RecordIndex * n)()
    rc = lib.ddws_index_shard(buf, len(buf), idx, n)
    if rc < 0:
        raise RuntimeError(f"{path}: native codec parse error {rc}")
    import numpy as np

    arr = np.ctypeslib.as_array(ctypes.cast(idx, ctypes.POINTER(ctypes.c_int64)),
                                shape=(n, 7))
    return buf, arr


def read_shard_contents_native(path: str) -> list[tuple[bytes, int]]:
    """Loader hot path: (content, label_idx) only — skips path/label string
    decoding and Record construction entirely."""
    buf, arr = _index(path)
    co = arr[:, 2].tolist()
    cl = arr[:, 3].tolist()
    li = (arr[:, 6] & 0xFFFFFFFF).astype("int32").tolist()
    return [(buf[o : o + l], i) for o, l, i in zip(co, cl, li)]


def read_shard_native(path: str) -> list[Record]:
    """Read a whole shard via the C++ index pass. Raises RuntimeError on codec
    errors and :class:`~ddw_tpu.native.build.NativeBuildError` if the library
    cannot be built."""
    buf, arr = _index(path)
    rows = arr.tolist()  # one bulk conversion to python ints
    out = []
    for po, pl_, co, cl, lo, ll, packed in rows:
        out.append(Record(
            path=buf[po : po + pl_].decode(),
            content=buf[co : co + cl],
            label=buf[lo : lo + ll].decode(),
            label_idx=ctypes.c_int32(packed & 0xFFFFFFFF).value,
        ))
    return out
