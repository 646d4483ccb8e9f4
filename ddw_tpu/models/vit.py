"""ViT — attention model family exercising tensor/sequence parallelism.

The reference has no attention model (SURVEY.md §2d: TP/SP "not required for
parity"), but long-context and model sharding are first-class axes of this
framework: ViT is the in-tree model whose attention runs through
``ddw_tpu.parallel.ring_attention`` when the mesh has a ``seq`` axis and whose
MLP/attention projections shard over ``model``. Patch-embed -> pre-LN transformer
blocks -> GAP head (same head contract as the CNNs, so trainer/serving are
model-agnostic).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddw_tpu.ops.flash_attention import flash_mha_seq_major


class FlashMHA(nn.Module):
    """Self-attention over the in-tree attention dispatch.

    Param layout matches ``nn.MultiHeadDotProductAttention`` —
    ``{query,key,value}/kernel [embed, heads, head_dim]``, ``out/kernel
    [heads, head_dim, embed]`` — so :data:`ddw_tpu.parallel.sharding
    .VIT_TP_RULES` shards it unchanged and checkpoints stay layout-stable.
    q, k, v go to :func:`ddw_tpu.ops.flash_attention.flash_mha_seq_major` as
    the projections give them, ``[B, S, H, head_dim]``, and its output to the
    out-projection: nothing is transposed a head, and on the one-block flash
    kernels (ViT-B/16's 196 patches) nothing is padded. ``lora_rank > 0`` puts
    adapters on the targeted projections (ddw_tpu.models.lora — base param
    paths unchanged)."""

    num_heads: int
    dtype: Any = jnp.bfloat16
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")

    @nn.compact
    def __call__(self, x):
        from ddw_tpu.models.lora import maybe_lora_dense

        d = x.shape[-1]
        if d % self.num_heads:
            raise ValueError(f"hidden {d} not divisible by heads {self.num_heads}")
        head_dim = d // self.num_heads

        def dense(name):
            return maybe_lora_dense((self.num_heads, head_dim), name,
                                    rank=self.lora_rank, alpha=self.lora_alpha,
                                    targets=self.lora_targets, dtype=self.dtype)

        # layer scopes of the compiled step (obs/step_scopes.py): metadata
        with jax.named_scope("attn_proj"):
            q = dense("query")(x)   # [B, S, H, hd]
            k = dense("key")(x)
            v = dense("value")(x)
        out = flash_mha_seq_major(q, k, v, causal=False)  # [B, S, H, hd]
        with jax.named_scope("attn_proj"):
            return maybe_lora_dense(d, "out", rank=self.lora_rank,
                                    alpha=self.lora_alpha,
                                    targets=self.lora_targets,
                                    dtype=self.dtype, contract_ndim=2)(out)


class MlpBlock(nn.Module):
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")

    @nn.compact
    def __call__(self, x):
        from ddw_tpu.models.lora import maybe_lora_dense

        d = x.shape[-1]

        def dense(feats, name):
            return maybe_lora_dense(feats, name, rank=self.lora_rank,
                                    alpha=self.lora_alpha,
                                    targets=self.lora_targets,
                                    dtype=self.dtype)

        with jax.named_scope("mlp"):
            h = dense(self.mlp_dim, "fc1")(x)
            h = nn.gelu(h)
            return dense(d, "fc2")(h)


class EncoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")

    @nn.compact
    def __call__(self, x, train: bool):
        h = nn.LayerNorm(dtype=jnp.float32)(x)
        h = FlashMHA(num_heads=self.num_heads, dtype=self.dtype,
                     lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
                     lora_targets=self.lora_targets, name="attn")(h)
        x = x + h
        h = nn.LayerNorm(dtype=jnp.float32)(x)
        h = MlpBlock(self.mlp_dim, dtype=self.dtype,
                     lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
                     lora_targets=self.lora_targets, name="mlp")(h)
        return x + h


class ViT(nn.Module):
    num_classes: int = 5
    patch: int = 16
    hidden: int = 192
    depth: int = 6
    # 4 heads (not 3): TP shards heads over the `model` axis, so the count must
    # divide small axis sizes. Changing this default changes q/k/v param shapes
    # — checkpoints/packages saved with another head count need num_heads set
    # explicitly at restore.
    num_heads: int = 4
    mlp_dim: int = 768
    dropout: float = 0.1
    freeze_base: bool = False
    dtype: Any = jnp.bfloat16
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")

    @nn.compact
    def __call__(self, x, train: bool = False):
        if self.lora_rank:
            from ddw_tpu.models.lora import validate_lora_targets

            validate_lora_targets(self.lora_targets)
        with jax.named_scope("embed"):      # patches and positions
            x = x.astype(self.dtype)
            x = nn.Conv(self.hidden, (self.patch, self.patch),
                        strides=self.patch, name="backbone_patch_embed",
                        dtype=self.dtype)(x)
            b, h, w, c = x.shape
            x = x.reshape(b, h * w, c)
            pos = self.param("pos_embed", nn.initializers.normal(0.02),
                             (1, h * w, c), jnp.float32)
            x = x + pos.astype(self.dtype)
        for i in range(self.depth):
            x = EncoderBlock(self.num_heads, self.mlp_dim, dtype=self.dtype,
                             lora_rank=self.lora_rank,
                             lora_alpha=self.lora_alpha,
                             lora_targets=self.lora_targets,
                             name=f"backbone_block{i}")(x, train)
        with jax.named_scope("head"):       # final norm, pooling, logits
            x = nn.LayerNorm(dtype=jnp.float32)(x)
            hfeat = jnp.mean(x.astype(jnp.float32), axis=1)
            hfeat = nn.Dropout(self.dropout, deterministic=not train,
                               name="head_dropout")(hfeat)
            return nn.Dense(self.num_classes, dtype=jnp.float32,
                            name="head")(hfeat)

    @staticmethod
    def frozen_prefixes(freeze_base: bool) -> tuple[str, ...]:
        return ()
