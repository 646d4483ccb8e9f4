"""Rotary position embeddings (RoPE, Su et al. 2021) — relative positions
for the long-context LM family.

The learned absolute table (``TransformerLM.pos_embed``) caps context at
``max_len`` and carries O(max_len * hidden) params; RoPE instead rotates each
(query, key) head-dim pair by an angle proportional to the token's absolute
position, which makes attention scores a function of *relative* distance
only (pinned by ``test_rope.py::test_scores_depend_on_relative_position``).
That is the property long-context training wants: positions extrapolate, and
sequence parallelism composes trivially — each shard rotates its OWN q/k by
its global positions (``offset = shard_index * s_local``) before the ring
hops, so K arrives at every peer already rotated and the ring kernel
(:mod:`ddw_tpu.parallel.ring_attention`) needs no position plumbing at all.
The KV-cached decode path rotates by the cache write position the same way.

Applied per head over ``[B, H, S, hd]`` with pair-split rotation:
``(x_even, x_odd) -> (x_even cosθ - x_odd sinθ, x_even sinθ + x_odd cosθ)``,
``θ(pos, 2i) = pos / theta^(2i/hd)``. Angles compute in f32 regardless of
activation dtype (bf16 cos/sin at position 10^5 would lose the low bits that
distinguish neighboring positions).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_angles(positions: jnp.ndarray, head_dim: int,
                theta: float = 10000.0) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) tables for integer ``positions [S]`` -> ``[S, hd/2]``
    (leading axes pass through: ``[B, S]`` -> ``[B, S, hd/2]``)."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    return _turns(positions, 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)))


def _turns(positions: jnp.ndarray, inv_freq: jnp.ndarray):
    """(cos, sin) of each position times each pair's frequency, float32."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  beta_fast: float, beta_slow: float,
                  original_len: int) -> jnp.ndarray:
    """YaRN's frequencies (Peng et al., arXiv:2309.00071, as DeepSeek-V2/V3
    apply them): pair ``i`` turns at ``theta^(-2i/hd)`` where it makes
    ``beta_fast`` turns or more in ``original_len`` positions, at that over
    ``factor`` where it makes ``beta_slow`` or fewer, and at the linear blend
    of the two over the pairs between (the ramp's ends are the floor and the
    ceiling of the two pairs' fractional indices). ``[hd/2]`` float32;
    ``factor = 1`` is plain RoPE."""
    pairs = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    plain = 1.0 / theta ** pairs

    def pair_turning(turns: float) -> float:
        return (head_dim * math.log(original_len / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_angles(positions: jnp.ndarray, head_dim: int, theta: float,
                factor: float, beta_fast: float, beta_slow: float,
                original_len: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`rope_angles` at :func:`yarn_inv_freq`'s frequencies, float32.
    The cos/sin scale ``mscale / mscale_all_dim`` of the published
    configurations is 1 and is not applied; the softmax scale's factor is the
    attention's (``models/lm.py``)."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    return _turns(positions, yarn_inv_freq(head_dim, theta, factor, beta_fast,
                                           beta_slow, original_len))


def yarn_softmax_factor(factor: float) -> float:
    """What YaRN multiplies the softmax scale by at ``mscale_all_dim = 1``:
    ``(0.1 ln factor + 1)^2``; 1 at a factor of 1 or less."""
    return (0.1 * math.log(factor) + 1.0) ** 2 if factor > 1 else 1.0


def mrope_angles(positions: jnp.ndarray, head_dim: int, theta: float,
                 sections: tuple[int, ...]) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-axis RoPE: ``positions [C, ..., S]`` holds one position a
    component (temporal, height, width) and ``sections`` says how many of the
    ``hd/2`` frequency pairs, in order, each component turns. Every pair keeps
    the frequency plain RoPE gives it, so equal components are plain RoPE."""
    if sum(sections) != head_dim // 2 or len(sections) != positions.shape[0]:
        raise ValueError(f"sections {sections} must split the {head_dim // 2} "
                         f"frequency pairs over {positions.shape[0]} "
                         f"position components")
    cos, sin = rope_angles(positions, head_dim, theta)    # [C, ..., S, hd/2]
    component = jnp.repeat(jnp.arange(len(sections)), jnp.asarray(sections),
                           total_repeat_length=head_dim // 2)
    pick = lambda t: jnp.take_along_axis(                 # noqa: E731
        t, component.reshape((1,) * (t.ndim - 1) + (-1,)), axis=0)[0]
    return pick(cos), pick(sin)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, *, seq_axis: int = -2,
               theta: float = 10000.0,
               sections: tuple[int, ...] = (),
               yarn: tuple = ()) -> jnp.ndarray:
    """Rotate ``x`` by its positions. The last axis is the head dim;
    ``seq_axis`` is where S lives (``-2`` for ``[B, H, S, hd]``, ``1`` for
    the pre-transpose ``[B, S, H, hd]`` projection layout). ``positions`` is
    ``[S]`` (shared across the batch) or ``[B, S]`` (per-row positions — the
    serving slot pool decodes rows at independent depths); with ``sections``
    (:func:`mrope_angles`) it carries a leading axis of position components;
    ``yarn = (factor, beta_fast, beta_slow, original_len)`` turns by
    :func:`yarn_angles`. Returns the same dtype as ``x``."""
    hd = x.shape[-1]
    axis = seq_axis % x.ndim
    if axis == x.ndim - 1:
        raise ValueError("seq_axis cannot be the head dim")
    s = x.shape[axis]
    if positions.shape[bool(sections):] not in ((s,), (x.shape[0], s)):
        raise ValueError(f"positions {positions.shape} must match seq dim "
                         f"{s} (axis {seq_axis}) or be [batch, {s}]")
    if sections:
        cos, sin = mrope_angles(positions, hd, theta, tuple(sections))
        positions = positions[0]
    elif yarn:
        cos, sin = yarn_angles(positions, hd, theta, *yarn)
    else:
        cos, sin = rope_angles(positions, hd, theta)
    # broadcast cos/sin to x's layout: S at `axis`, hd/2 at the last axis
    # (and B leading when positions are per-row)
    bshape = [1] * x.ndim
    bshape[axis] = s
    bshape[-1] = hd // 2
    if positions.ndim == 2:
        bshape[0] = x.shape[0]
    cos = cos.reshape(bshape)
    sin = sin.reshape(bshape)
    x32 = x.astype(jnp.float32)
    x_even = x32[..., 0::2]
    x_odd = x32[..., 1::2]
    out_even = x_even * cos - x_odd * sin
    out_odd = x_even * sin + x_odd * cos
    # re-interleave: [..., hd/2, 2] -> [..., hd]
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
