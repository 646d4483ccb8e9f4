"""The one rule for when a Pallas kernel runs in the interpreter.

Interpret mode is a CPU stand-in for tests: it is chosen when JAX's default
backend is ``cpu`` and in no other case. On any other backend — whatever its
name — the kernel is handed to the compiler, which compiles it or raises; a
kernel never runs interpreted, and never gives way to an XLA fallback, on a
device without saying so.
"""

from __future__ import annotations

import jax


def interpret_by_default() -> bool:
    return jax.default_backend() == "cpu"
