"""Pallas depthwise 3x3 convolution — the MobileNet family's HBM-bound op.

A depthwise conv moves ~1 byte per FLOP (9 MACs per element loaded), so on a
v5e it is bandwidth-bound at ~819 GB/s and its step-time floor is
``2 * B*H*W*C * bytes / BW`` (read + write; the reference's cuDNN stack has
dedicated depthwise kernels for exactly this reason). XLA lowers
``feature_group_count=C`` convs through its general conv path; this kernel is
the hand-written alternative that reads each input tile into VMEM ONCE and
computes all nine taps from registers/VMEM:

- grid over the batch; one [H, W, C] image block per step (every depthwise
  layer in MobileNetV2-224 has H <= 112; with C padded to 128 lanes and the
  f32 working copies Mosaic asks for up to 22 MiB of scoped VMEM at 112x112x32
  and 56x56x144, above its 16 MiB default — hence ``_VMEM_LIMIT_BYTES``);
- taps are static slices of the zero-padded block, accumulated in f32 on the
  VPU (8x128 lanes; C is the lane dim);
- backward is two more Pallas kernels: dx = the same conv with spatially
  flipped taps; dw accumulates the 9 per-channel correlations across the
  batch grid (constant output index_map -> the [3,3,C] block stays resident).

The kernel is stride 1 only (stride-2 depthwise appears 4x in MobileNetV2 vs
~13 stride-1 layers; those stay on the XLA grouped conv). ``impl="pallas"``
means the kernel: compiled by Mosaic on a device, interpreted on the CPU
backend (:mod:`ddw_tpu.ops.backend`), never swapped for XLA. Numerics are
pinned against the XLA path in ``tests/test_depthwise.py``, including
gradients.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddw_tpu.ops.backend import interpret_by_default

# Scoped-VMEM ceiling for the whole-image blocks (see module doc); a v5e core
# has 128 MiB.
_VMEM_LIMIT_BYTES = 64 * 2**20


def _xla_depthwise(x: jnp.ndarray, w: jnp.ndarray, stride: int) -> jnp.ndarray:
    """The XLA grouped conv (and the numerics reference). ``w`` is [3, 3, C]."""
    c = x.shape[-1]
    return lax.conv_general_dilated(
        x, w[:, :, None, :], window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c)


def _fwd_kernel(x_ref, w_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)           # [H, W, C]
    h, wd, c = x.shape
    xp = jnp.pad(x, ((1, 1), (1, 1), (0, 0)))
    acc = jnp.zeros((h, wd, c), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            acc += xp[dy:dy + h, dx:dx + wd, :] * w_ref[dy, dx, :].astype(jnp.float32)
    o_ref[0] = acc.astype(o_ref.dtype)


def _dw_kernel(x_ref, g_ref, dw_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    x = x_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    h, wd, c = x.shape
    xp = jnp.pad(x, ((1, 1), (1, 1), (0, 0)))
    for dy in range(3):
        for dx in range(3):
            part = jnp.sum(xp[dy:dy + h, dx:dx + wd, :] * g, axis=(0, 1))
            dw_ref[dy, dx, :] += part.astype(dw_ref.dtype)


def _pallas_fwd(x, w, interpret):
    b, h, wd, c = x.shape
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, wd, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, c), lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, wd, c), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, wd, c), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, w)


def _pallas_dw(x, g, interpret):
    b, h, wd, c = x.shape
    return pl.pallas_call(
        _dw_kernel,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, wd, c), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, wd, c), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((3, 3, c), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, 3, c), jnp.float32),
        # the dw block accumulates across grid steps -> sequential grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _depthwise_pallas(x, w, interpret=False):
    return _pallas_fwd(x, w, interpret)


def _vjp_fwd(x, w, interpret):
    return _pallas_fwd(x, w, interpret), (x, w)


def _vjp_bwd(interpret, res, g):
    x, w = res
    # dx: correlate g with the spatially flipped taps (same kernel shape)
    dx = _pallas_fwd(g.astype(x.dtype), w[::-1, ::-1, :], interpret)
    dw = _pallas_dw(x, g, interpret).astype(w.dtype)
    return dx, dw


_depthwise_pallas.defvjp(_vjp_fwd, _vjp_bwd)


def depthwise_conv3x3(x: jnp.ndarray, w: jnp.ndarray, *, stride: int = 1,
                      impl: str = "xla") -> jnp.ndarray:
    """SAME depthwise 3x3 conv, NHWC; ``w`` is [3, 3, C].

    ``impl``: "xla" (grouped conv) or "pallas" (the kernel, stride 1 only —
    whoever asks for it gets it or an error, never the other one).
    """
    if w.shape[:2] != (3, 3) or w.ndim != 3:
        raise ValueError(f"w must be [3, 3, C], got {w.shape}")
    if x.shape[-1] != w.shape[-1]:
        raise ValueError(f"channel mismatch: x {x.shape} vs w {w.shape}")
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r}")
    if impl == "pallas":
        if stride != 1:
            raise ValueError("the Pallas depthwise kernel supports stride 1; "
                             "use impl='xla' for strided layers")
        return _depthwise_pallas(x, w, interpret_by_default())
    return _xla_depthwise(x, w, stride)


class DepthwiseConv3x3(nn.Module):
    """Drop-in for the depthwise ``nn.Conv(C, (3,3), feature_group_count=C,
    use_bias=False)``: same param name ("kernel") and shape ``[3, 3, 1, C]``,
    same init and dtype promotion — give it the name the nn.Conv would have
    gotten and the checkpoint format is unchanged. Routes the compute through
    :func:`depthwise_conv3x3` with this module's ``impl``.
    """

    features: int
    strides: int = 1
    dtype: object = jnp.bfloat16
    impl: str = "xla"

    @nn.compact
    def __call__(self, x):
        if x.shape[-1] != self.features:
            raise ValueError(f"depthwise conv needs C_in == C_out, got "
                             f"{x.shape[-1]} vs {self.features}")
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (3, 3, 1, self.features), jnp.float32)
        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        return depthwise_conv3x3(x, kernel[:, :, 0, :], stride=self.strides,
                                 impl=self.impl)
