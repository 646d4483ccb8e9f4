"""Weights from ``--seed``, made by the benchmark and handed to both sides.

One jitted call makes every leaf on the device, in float32 (the type both
trainers keep their parameters in). The program's own initialisation still
runs inside ``fit`` (it is part of ``setup_s``); its values are replaced
before the first step (``harness/step_probe.py``), so that the plain
reference — which imports nothing of the program and takes nothing the program
has made — starts from the same numbers.

A spec maps a leaf's name to ``(shape, kind)``. Kinds: ``w`` (matrices,
embeddings: N(0, 0.02)), ``bias`` (N(0, 0.02), not zero, so that every leaf has
a gradient path that precision can move) and ``gain`` (1 + N(0, 0.02)).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: ``--seed`` may pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def seeded_weights(key: jax.Array, spec: dict) -> dict:
    """Traceable: call it inside the jitted function that needs the values."""
    out = {}
    for name, (shape, kind) in spec.items():
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        noise = STD * jax.random.normal(k, shape, jnp.float32)
        out[name] = 1.0 + noise if kind == "gain" else noise
    return out
