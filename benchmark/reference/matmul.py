"""The reference's matrix product, in the precision asked for.

``f32``: float32 operands, ``Precision.HIGHEST`` (on a TPU a float32 product
otherwise runs as one bfloat16 pass). ``bf16``: operands rounded to bfloat16,
float32 accumulation — what the configurations state. ``fp8``: operands
rounded to float8_e4m3fn with one scale per tensor (amax to 448), the step
below bfloat16 that a later PR would be tempted by; it is the control that
the ``correct`` comparison has to fail (PERF.md section 2). Rounding is
straight-through, so the backward products see the rounded partner operand.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

E4M3_MAX = 448.0


def _ste(x, rounded):
    return x + lax.stop_gradient(rounded - x)


def _round(x, precision: str):
    if precision == "f32":
        return x
    if precision == "bf16":
        # reduce_precision, not a cast there and back: XLA may drop such a
        # pair of casts (xla_allow_excess_precision)
        return _ste(x, lax.reduce_precision(x, exponent_bits=8,
                                            mantissa_bits=7))
    if precision == "fp8":
        scale = E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
        return _ste(x, q)
    raise ValueError(f"unknown precision {precision!r}")


def make_einsum(precision: str):
    def einsum(spec, a, b):
        return jnp.einsum(spec, _round(a, precision), _round(b, precision),
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return einsum
