"""MobileNetV2 in flax.linen — the reference's flagship backbone.

The reference builds ``MobileNetV2(include_top=False, weights='imagenet')`` frozen,
plus GlobalAveragePooling -> Dropout(0.5) -> Dense(num_classes) head
(``Part 1 - Distributed Training/02_model_training_single_node.py:159-178``). This is
that architecture (Sandler et al. 2018: inverted residuals, linear bottlenecks,
ReLU6) implemented TPU-first:

- NHWC layout with channel counts rounded to multiples of 8 (the standard
  divisible-by-8 rule — also what XLA tiles best onto the MXU);
- compute dtype bfloat16 (params float32) so convs hit the MXU at full rate;
- transfer-learning mode: ``backbone``/``head`` are separate top-level param
  subtrees, so the trainer freezes the base by masking optimizer updates on the
  ``backbone`` prefix and running its BatchNorm in inference mode — the
  ``base_model.trainable = False`` semantics of Keras (reference ``:169``, which
  also stops BN statistic updates).

Pretrained ImageNet weights are an optional artifact (``ModelCfg.pretrained_path``,
converted offline); absent weights, the architecture trains from scratch (SURVEY.md
§7 hard-part 1 option b).
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

# (expansion t, out channels c, repeats n, stride s) — Sandler et al. Table 2.
_INVERTED_RESIDUAL_CFG: Sequence[tuple[int, int, int, int]] = (
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
)


def _make_divisible(v: float, divisor: int = 8) -> int:
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


class ConvBN(nn.Module):
    features: int
    kernel: tuple[int, int] = (3, 3)
    strides: int = 1
    groups: int = 1
    act: bool = True
    bn_momentum: float = 0.9
    dtype: Any = jnp.bfloat16
    s2d: bool = False  # stem trick: identical math, MXU-friendly channel depth
    dw_impl: str = "xla"  # depthwise layers: "xla" grouped conv, or
                          # "pallas" — the ddw_tpu.ops.depthwise_conv kernel
                          # on every stride-1 layer (the kernel has no strided
                          # form; the four stride-2 layers keep XLA)

    @nn.compact
    def __call__(self, x, train: bool):
        if self.dw_impl not in ("xla", "pallas"):
            raise ValueError(f"unknown dw_impl {self.dw_impl!r}")
        depthwise = self.groups > 1 and self.groups == x.shape[-1]
        if (depthwise and self.dw_impl == "pallas" and self.kernel == (3, 3)):
            from ddw_tpu.ops.depthwise_conv import DepthwiseConv3x3

            # Same param path/shape as the nn.Conv branch (see module doc).
            x = DepthwiseConv3x3(
                self.features, strides=self.strides, dtype=self.dtype,
                impl="pallas" if self.strides == 1 else "xla",
                name="Conv_0")(x)
        else:
            from ddw_tpu.ops.s2d_conv import conv_or_s2d

            x = conv_or_s2d(self.features, self.kernel, strides=self.strides,
                            groups=self.groups, dtype=self.dtype,
                            s2d=self.s2d)(x)
        # Default momentum 0.9, not Keras's 0.99: the reference only ever runs
        # BN with a pretrained FROZEN base (stats never update, momentum
        # irrelevant); for from-scratch training 0.99 needs ~500 steps before
        # running stats are usable, leaving eval broken for entire short runs.
        # ModelCfg.bn_momentum=0.99 restores the Keras value for parity runs
        # that finetune an unfrozen pretrained base. epsilon stays at Keras's
        # 1e-3 so converted pretrained weights reproduce exactly.
        x = nn.BatchNorm(use_running_average=not train,
                         momentum=self.bn_momentum, epsilon=1e-3,
                         dtype=jnp.float32)(x)
        if self.act:
            x = jnp.minimum(nn.relu(x), 6.0).astype(self.dtype)  # ReLU6
        return x


class InvertedResidual(nn.Module):
    out_ch: int
    stride: int
    expand: int
    bn_momentum: float = 0.9
    dtype: Any = jnp.bfloat16
    dw_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool):
        in_ch = x.shape[-1]
        bn = self.bn_momentum
        h = x
        if self.expand != 1:
            h = ConvBN(in_ch * self.expand, (1, 1), bn_momentum=bn,
                       dtype=self.dtype)(h, train)
        # depthwise
        h = ConvBN(h.shape[-1], (3, 3), strides=self.stride, groups=h.shape[-1],
                   bn_momentum=bn, dtype=self.dtype,
                   dw_impl=self.dw_impl)(h, train)
        # linear bottleneck projection (no activation)
        h = ConvBN(self.out_ch, (1, 1), act=False, bn_momentum=bn,
                   dtype=self.dtype)(h, train)
        if self.stride == 1 and in_ch == self.out_ch:
            h = h + x
        return h


class MobileNetV2Backbone(nn.Module):
    width_mult: float = 1.0
    bn_momentum: float = 0.9
    dtype: Any = jnp.bfloat16
    stem_s2d: bool = False
    dw_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool):
        bn = self.bn_momentum
        x = x.astype(self.dtype)
        x = ConvBN(_make_divisible(32 * self.width_mult), (3, 3), strides=2,
                   bn_momentum=bn, dtype=self.dtype, s2d=self.stem_s2d)(x, train)
        for t, c, n, s in _INVERTED_RESIDUAL_CFG:
            out_ch = _make_divisible(c * self.width_mult)
            for i in range(n):
                x = InvertedResidual(out_ch, s if i == 0 else 1, t,
                                     bn_momentum=bn, dtype=self.dtype,
                                     dw_impl=self.dw_impl)(x, train)
        last = _make_divisible(1280 * max(1.0, self.width_mult))
        x = ConvBN(last, (1, 1), bn_momentum=bn, dtype=self.dtype)(x, train)
        return x


class MobileNetV2(nn.Module):
    """Backbone + transfer head. ``freeze_base`` reproduces Keras
    ``base_model.trainable=False`` (reference ``:169``): backbone BN runs in
    inference mode; the trainer additionally masks backbone param updates."""

    num_classes: int = 5
    width_mult: float = 1.0
    dropout: float = 0.5
    freeze_base: bool = True
    bn_momentum: float = 0.9
    dtype: Any = jnp.bfloat16
    stem_s2d: bool = False
    dw_impl: str = "xla"

    @nn.compact
    def __call__(self, x, train: bool = False):
        base_train = train and not self.freeze_base
        feats = MobileNetV2Backbone(self.width_mult, self.bn_momentum,
                                    self.dtype, stem_s2d=self.stem_s2d,
                                    dw_impl=self.dw_impl,
                                    name="backbone")(x, base_train)
        if self.freeze_base:
            # Keras trainable=False computes no base gradients: the tape stops at
            # the head input. stop_gradient guarantees XLA drops the backbone
            # backward pass instead of relying on DCE of the masked updates.
            feats = jax.lax.stop_gradient(feats)
        # GAP -> Dropout -> Dense logits (reference :171-178; logits, not softmax —
        # loss is SparseCategoricalCrossentropy(from_logits=True), :202)
        h = jnp.mean(feats.astype(jnp.float32), axis=(1, 2))
        h = nn.Dropout(self.dropout, deterministic=not train, name="head_dropout")(h)
        logits = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(h)
        return logits

    @staticmethod
    def frozen_prefixes(freeze_base: bool) -> tuple[str, ...]:
        """Top-level param-tree keys the optimizer must not update in transfer mode."""
        return ("backbone",) if freeze_base else ()
