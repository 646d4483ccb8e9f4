"""Shared lazy g++ build/load for the native components (codec, decode pipeline).

The toolchain (g++, libjpeg) is part of the environment contract; pybind11 is
not, so all native modules use a plain C ABI loaded via ctypes. A library that
cannot be built or loaded is an error carrying the compiler's own message —
callers never drop to a slower Python path on their own: a run that quietly
decoded through PIL would still look healthy, only slower.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading


class NativeBuildError(RuntimeError):
    """g++ refused the source, or the built library would not load."""


class LazyLibrary:
    """Builds ``src`` -> ``lib`` with g++ on first use (if stale), then loads it.

    ``configure(cdll)`` sets restype/argtypes once after load. Thread-safe;
    concurrent processes build to a per-pid temp path and ``os.replace`` so no
    process ever dlopens a half-written .so. A failure latches: every later
    :meth:`load` re-raises the first error without re-running the compiler.
    """

    def __init__(self, src: str, lib: str, extra_flags: tuple[str, ...] = (),
                 configure=None):
        self.src = src
        self.lib_path = lib
        self.extra_flags = tuple(extra_flags)
        self.configure = configure
        self._lock = threading.Lock()
        self._lib: ctypes.CDLL | None = None
        self._error: NativeBuildError | None = None

    def _build(self) -> None:
        tmp = f"{self.lib_path}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", self.src,
               "-o", tmp, *self.extra_flags]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, self.lib_path)
        except (OSError, subprocess.SubprocessError) as e:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            stderr = getattr(e, "stderr", None) or b""
            raise NativeBuildError(
                f"native build failed: {' '.join(cmd)}\n{e}\n"
                f"{stderr.decode(errors='replace')}") from e

    def load(self) -> ctypes.CDLL:
        with self._lock:
            if self._error is not None:
                raise self._error
            if self._lib is not None:
                return self._lib
            try:
                try:
                    stale = (not os.path.exists(self.lib_path)
                             or os.path.getmtime(self.lib_path)
                             < os.path.getmtime(self.src))
                except OSError:
                    # source missing (deployment shipping only the built
                    # .so): use the existing library if present
                    stale = not os.path.exists(self.lib_path)
                if stale:
                    self._build()
                try:
                    lib = ctypes.CDLL(self.lib_path)
                except OSError as e:
                    raise NativeBuildError(
                        f"cannot load {self.lib_path}: {e}") from e
                if self.configure is not None:
                    self.configure(lib)
                self._lib = lib
            except NativeBuildError as e:
                self._error = e
                raise
        return self._lib
