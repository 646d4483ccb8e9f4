"""The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as a layer's one mixer:

    [z | xBC | dt] = u W_in           (inner | inner + 2 G N | H wide)
    xBC <- silu(causal depthwise conv over ssm_conv tokens of xBC, with bias)
    x [H, P], B [G, N], C [G, N] = split(xBC)
    dt = softplus(dt + dt_bias + ssm_dt_shift);  A = -exp(A_log), a head
    y = scan(x, dt, A, B, C) + D x    (ops/ssd.py: the chunked form)
    y <- RMSNorm over each group's inner / G channels of (y * silu(z)), gain
    out = y W_out

``inner = ssm_heads * ssm_head_dim`` (set by the heads, not by an expansion
factor). The projections run in the activations' type; ``dt``, the decays,
the carried state, the convolution's sum and the gated norm in float32. No
bias but the convolution's. Training only: one device's whole sequence, no
cache of the state (ROADMAP M5's serving half).

Sows the counter ``ssm_chunk_carry``: the mean over heads and chunks of what
share of a state crosses a chunk, ``exp(sum over the chunk of dt A)``.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddw_tpu.ops.ssd import causal_conv1d, ssd_scan
from ddw_tpu.utils.config import LayerSpec


DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4   # the family's published range


def _taps_init(key, shape, dtype):
    return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype):
    """softplus^-1 of a step drawn log-uniformly from the published range."""
    lo, hi = jnp.log(DT_MIN), jnp.log(DT_MAX)
    dt = jnp.maximum(jnp.exp(lo + (hi - lo) * jax.random.uniform(
        key, shape, dtype)), DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    layer: LayerSpec
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        spec = self.layer
        h, p, g, n = (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_groups,
                      spec.ssm_state)
        if min(h, p, g, n) < 1 or h % g:
            raise ValueError(f"a Mamba-2 layer needs ssm_heads {h}, "
                             f"ssm_head_dim {p}, ssm_state {n} > 0 and "
                             f"ssm_groups {g} dividing the heads")
        bsz, s, d = u.shape
        inner, conv_dim = h * p, h * p + 2 * g * n
        init = nn.initializers.lecun_normal()
        w_in = self.param("in_proj", init, (d, inner + conv_dim + h),
                          jnp.float32)
        conv_w = self.param("conv_kernel", _taps_init,
                            (spec.ssm_conv, conv_dim), jnp.float32)
        conv_b = self.param("conv_bias", nn.initializers.zeros, (conv_dim,),
                            jnp.float32)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
        gain = self.param("norm_scale", nn.initializers.ones, (inner,),
                          jnp.float32)
        w_out = self.param("out_proj", init, (inner, d), jnp.float32)

        with jax.named_scope("ssm_proj"):
            u = u.astype(self.dtype)
            zx = jnp.dot(u, w_in[:, :inner + conv_dim].astype(self.dtype))
            z, xbc = zx[..., :inner], zx[..., inner:]
            # dt leaves its product in float32: it is summed over a chunk
            dt = jnp.dot(u, w_in[:, inner + conv_dim:].astype(self.dtype),
                         preferred_element_type=jnp.float32)
        with jax.named_scope("ssm_conv"):
            xbc = nn.silu(causal_conv1d(xbc, conv_w, conv_b)).astype(
                self.dtype)
        x = xbc[..., :inner].reshape(bsz, s, h, p)
        b = xbc[..., inner:inner + g * n].reshape(bsz, s, g, n)
        c = xbc[..., inner + g * n:].reshape(bsz, s, g, n)
        with jax.named_scope("ssm_scan"):
            dt = jax.nn.softplus(dt + dt_bias + spec.ssm_dt_shift)
            y, crossing = ssd_scan(x, dt, -jnp.exp(a_log), b, c,
                                   spec.ssm_chunk)
            y = y + skip[:, None] * x.astype(jnp.float32)
        self.sow("intermediates", "counters",
                 {"ssm_chunk_carry": jnp.mean(crossing)})
        with jax.named_scope("ssm_gate_norm"):
            y = (y.reshape(bsz, s, inner)
                 * nn.silu(z.astype(jnp.float32))).reshape(bsz, s, g, -1)
            y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                                  + spec.norm_eps)
            y = (y.reshape(bsz, s, inner) * gain).astype(self.dtype)
        with jax.named_scope("ssm_proj"):
            return jnp.dot(y, w_out.astype(self.dtype))
