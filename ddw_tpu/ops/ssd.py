"""The Mamba-2 mixer's two sequence operations: the causal depthwise
convolution and the state-space scan in its chunked (SSD) form.

A head of the scan carries a state ``h_t [P, N]``:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t

with ``A < 0`` a number a head, ``dt_t > 0`` a number a head and token, ``x_t
[P]`` a head's input and ``B_t``, ``C_t [N]`` shared by the heads of a group
(Dao & Gu, arXiv:2405.21060). Token by token that is ``S`` steps of a few
operations each. The chunked form computes the same ``y`` from matrix
products: inside a chunk of ``Q`` tokens as a masked ``Q x Q`` product,
``y_i += sum_{j <= i} (C_i . B_j) exp(l_i - l_j) dt_j x_j`` with ``l`` the
cumulative sum of ``dt A`` inside the chunk; between chunks by the state a
chunk hands to the next, ``H_{c+1} = exp(l_Q) H_c + sum_j exp(l_Q - l_j) dt_j
x_j B_j^T`` and ``y_i += exp(l_i) H_c C_i``.

``dt``, ``l``, every decay and the carried state are float32; the products
take operands in the activations' type and accumulate in float32. Every
exponent is a difference of cumulative sums of non-positive numbers taken the
way that keeps it non-positive (later minus earlier), never a quotient of
exponentials. The backward pass is autodiff of this form: the same products
transposed, and the recurrence over chunks run backwards.

No kernel: the products land as XLA's. A chunk's ``[H, Q, Q]`` decay mask is
the scan's memory traffic (float32, 64 MB a layer and row of 8,192 tokens at
64 heads), which is why the scan is bound by memory and not by the MXU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def causal_conv1d(x, w, b=None):
    """Causal depthwise convolution along the sequence: ``x [B, S, C]``, taps
    ``w [K, C]`` (``w[K - 1]`` multiplies the token itself, ``w[0]`` the one
    ``K - 1`` before it; tokens before the row's first read zero), bias ``b
    [C]``. Computed in float32, returned in float32."""
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + s] * w[j].astype(jnp.float32) for j in range(k))
    return y if b is None else y + b.astype(jnp.float32)


def carry_states(local, decay):
    """The state each chunk STARTS from: ``local [B, C, ...]`` what a chunk's
    own tokens leave in the state at its end, ``decay [B, C, H']`` (broadcast
    over the state's trailing dims) what is left of a state that crosses the
    whole chunk. ``H_0 = 0``, ``H_{c+1} = decay_c H_c + local_c``, float32;
    returns ``[B, C, ...]``, chunk ``c``'s entry ``H_c``."""
    decay = decay.reshape(decay.shape + (1,) * (local.ndim - decay.ndim))

    def step(h, xs):
        d, s = xs
        return d * h + s, h

    _, before = lax.scan(step, jnp.zeros_like(local[:, 0]),
                         (jnp.moveaxis(decay, 1, 0),
                          jnp.moveaxis(local, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


def ssd_scan(x, dt, a, b, c, chunk: int):
    """``x [B, S, H, P]``, ``dt [B, S, H]`` (after its softplus), ``a [H]``
    (negative), ``b``, ``c [B, S, G, N]`` with ``G`` dividing ``H`` (head
    ``h`` reads group ``h // (H / G)``). Returns ``y [B, S, H, P]`` in float32
    (without the ``D x`` skip, which the mixer adds) and ``crossing [B, C,
    H]``: what share of a state crosses each chunk, ``exp(sum over the chunk
    of dt A)``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    if h % g:
        raise ValueError(f"{g} groups do not divide {h} heads")
    r = h // g
    pad = -s % chunk
    if pad:
        # a token with dt = 0 neither decays the state nor adds to it
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    dtype = x.dtype
    f32 = jnp.float32
    dt = dt.astype(f32)
    # [B, C, Q, G, R(, ...)]: chunks, then heads by group
    log_decay = (dt * a.astype(f32)).reshape(bsz, nc, chunk, g, r)
    xd = (x.astype(f32) * dt[..., None]).astype(dtype).reshape(
        bsz, nc, chunk, g, r, p)
    b = b.reshape(bsz, nc, chunk, g, n)
    c = c.reshape(bsz, nc, chunk, g, n)
    cum = jnp.cumsum(log_decay, axis=2)                 # l_i, inclusive
    total = cum[:, :, -1]                               # [B, C, G, R]

    # inside a chunk: (C_i . B_j) exp(l_i - l_j) for j <= i
    scores = jnp.einsum("bcign,bcjgn->bcgij", c, b, preferred_element_type=f32)
    by_head = jnp.moveaxis(cum, 2, -1)                  # [B, C, G, R, Q]
    later = by_head[..., :, None] - by_head[..., None, :]
    causal = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None]
    mask = jnp.exp(jnp.where(causal, later, -jnp.inf))  # [B, C, G, R, i, j]
    mixed = (scores[:, :, :, None] * mask).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mixed, xd,
                   preferred_element_type=f32)

    # between chunks: what a chunk leaves, what the next starts from
    to_end = jnp.exp(total[:, :, None] - cum)           # [B, C, Q, G, R]
    left = jnp.einsum("bcjgn,bcjgrp->bcgrpn", b,
                      (xd.astype(f32) * to_end[..., None]).astype(dtype),
                      preferred_element_type=f32)
    start = carry_states(left, jnp.exp(total))          # [B, C, G, R, P, N]
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", c, start.astype(dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.reshape(bsz, nc * chunk, h, p)[:, :s]
    return y, jnp.exp(total).reshape(bsz, nc, h)
