"""SmallCNN — a fast from-scratch CNN for tests and CPU-capable runs.

Fills the "small CNN, flowers JPEG subset, CPU, 1 epoch" baseline config
(BASELINE.json configs[0]) and keeps the unit-test suite fast. Same
head contract as MobileNetV2 (GAP -> Dropout -> Dense logits) so the trainer and
serving paths are model-agnostic. Stateless normalization (GroupNorm) — no
batch_stats collection — so seeded 1-device vs N-device equivalence tests are exact.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp


class SmallCNN(nn.Module):
    num_classes: int = 5
    width: int = 32
    dropout: float = 0.5
    freeze_base: bool = False  # accepted for API parity; no pretrained base to freeze
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = x.astype(self.dtype)
        for i, mult in enumerate((1, 2, 4)):
            x = nn.Conv(self.width * mult, (3, 3), strides=2 if i else 1,
                        padding="SAME", use_bias=False, dtype=self.dtype, name=f"backbone_conv{i}")(x)
            x = nn.GroupNorm(num_groups=8, dtype=jnp.float32)(x)
            x = nn.relu(x).astype(self.dtype)
        h = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        h = nn.Dropout(self.dropout, deterministic=not train, name="head_dropout")(h)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(h)

    @staticmethod
    def frozen_prefixes(freeze_base: bool) -> tuple[str, ...]:
        return ()
