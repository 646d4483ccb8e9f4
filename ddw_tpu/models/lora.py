"""LoRA — low-rank adaptation, the parameter-efficient transfer path.

The reference's transfer story is "freeze the backbone, train the head"
(``02_model_training_single_node.py:164-178``). LoRA (Hu et al. 2021) is that
idea generalized to attention-era models: the base weights stay frozen and
each targeted projection learns a rank-``r`` update ``ΔW = A B · α/r``. This
module brings it to the LM family the same way ``freeze_base`` serves the CNN
families — and it is a natural fit for the TPU step: the adapter matmuls are
tiny, XLA fuses them into the existing projection, and the optimizer state
shrinks from O(params) to O(r·(d_in+d_out)) per target, which matters exactly
where ZeRO/TP matter.

Design constraints:

- **Param-path compatibility.** :class:`LoRADenseGeneral` declares ``kernel``
  / ``bias`` with the same names, shapes, and dtypes as the
  ``nn.DenseGeneral`` it replaces, and adds ``lora_a`` / ``lora_b`` beside
  them. A base (non-LoRA) checkpoint grafts into a LoRA model with
  :func:`merge_base_params`; at init the adapted output EQUALS the base
  output (``lora_b`` starts at zero), so fine-tuning starts from exactly the
  pretrained function.
- **Freezing via the same optimizer-masking idiom** the CNN transfer mode
  uses (``ddw_tpu.train.step.make_optimizer``): :func:`lora_optimizer` wraps
  any optax transform with ``set_to_zero`` on every non-adapter leaf.
"""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

LORA_PARAM_NAMES = ("lora_a", "lora_b")


class LoRADenseGeneral(nn.Module):
    """``nn.DenseGeneral`` plus a rank-``rank`` adapter. ``features`` may be
    an int (Dense) or a tuple (DenseGeneral, e.g. ``(heads, head_dim)``);
    ``contract_ndim`` is how many trailing input dims the projection
    contracts (2 for the attention output projection's ``axis=(-2, -1)``).
    """

    features: int | Sequence[int]
    rank: int
    alpha: float = 16.0
    use_bias: bool = True
    dtype: Any = jnp.bfloat16
    contract_ndim: int = 1

    @nn.compact
    def __call__(self, x):
        feats = (tuple(self.features) if isinstance(self.features, (tuple, list))
                 else (int(self.features),))
        cn = self.contract_ndim
        in_dims = tuple(x.shape[-cn:])
        if self.rank <= 0:
            raise ValueError(f"rank must be positive, got {self.rank}")
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (*in_dims, *feats), jnp.float32)
        bias = (self.param("bias", nn.initializers.zeros, feats, jnp.float32)
                if self.use_bias else None)
        # LoRA init (Hu et al. §4.1): A random, B zero — ΔW starts at 0 and
        # the module computes exactly the base projection until training moves
        # lora_b.
        lora_a = self.param("lora_a", nn.initializers.lecun_normal(),
                            (*in_dims, self.rank), jnp.float32)
        lora_b = self.param("lora_b", nn.initializers.zeros,
                            (self.rank, *feats), jnp.float32)

        x, kernel, bias, lora_a, lora_b = nn.dtypes.promote_dtype(
            x, kernel, bias, lora_a, lora_b, dtype=self.dtype)
        n_feat = len(feats)
        cdims_x = tuple(range(x.ndim - cn, x.ndim))
        contract = ((cdims_x, tuple(range(cn))), ((), ()))
        y = jax.lax.dot_general(x, kernel, contract)
        a = jax.lax.dot_general(x, lora_a, contract)  # [..., rank]
        delta = jax.lax.dot_general(
            a, lora_b, (((a.ndim - 1,), (0,)), ((), ())))
        y = y + delta * (self.alpha / self.rank)
        if bias is not None:
            y = y + jnp.reshape(bias, (1,) * (y.ndim - n_feat) + feats)
        return y


# Projections the attention families (LM, ViT) route through
# maybe_lora_dense. Anything else in lora_targets is a config error.
LM_LORA_TARGETS = ("query", "key", "value", "out", "fc1", "fc2")


def row_lora_delta(x, a, b, contract_ndim: int = 1):
    """Per-ROW adapter delta for heterogeneous-adapter batched serving
    (S-LoRA, arXiv 2311.03285): each batch row carries its OWN ``(A, B)``
    pair, gathered from an adapter stack by the row's adapter index, so one
    decode tick serves many adapters (and the base model) at once.

    ``x`` is ``[B, S, *in_dims]``; ``a`` is ``[B, *in_dims, r]``; ``b`` is
    ``[B, r, *feats]`` with any ``alpha/rank`` scaling already folded in
    (:class:`ddw_tpu.serve.adapters.AdapterPool` pre-scales at load).
    Returns ``[B, S, *feats]``. A zero ``b`` row (the reserved null adapter)
    contributes exactly ``+0.0`` — the base-model row in a mixed batch stays
    token-identical to an adapter-free engine.
    """
    a = a.astype(x.dtype)
    b = b.astype(x.dtype)
    cn = contract_ndim
    xdims = tuple(range(2, 2 + cn))          # trailing input dims of [B,S,*]
    adims = tuple(range(1, 1 + cn))          # matching dims of [B,*in,r]
    h = jax.lax.dot_general(x, a, ((xdims, adims), ((0,), (0,))))  # [B, S, r]
    return jax.lax.dot_general(h, b, (((2,), (1,)), ((0,), (0,))))


def validate_lora_targets(targets: Sequence[str],
                          known: Sequence[str] = LM_LORA_TARGETS) -> None:
    """Raise on a target name the model does not route through
    :func:`maybe_lora_dense` — a typo would otherwise silently adapt
    nothing."""
    bad = set(targets) - set(known)
    if bad:
        raise ValueError(f"unknown lora_targets {sorted(bad)}; this model "
                         f"can adapt {list(known)}")


def maybe_lora_dense(features, name: str, *, rank: int, alpha: float,
                     targets: Sequence[str], dtype, contract_ndim: int = 1,
                     use_bias: bool = True):
    """The one dispatch point between a plain projection and its LoRA
    version: returns ``LoRADenseGeneral`` when ``name`` is targeted, else the
    equivalent ``nn.DenseGeneral`` — identical param paths either way, so the
    checkpoint format does not fork on the flag."""
    if rank and name in tuple(targets):
        return LoRADenseGeneral(features, rank=rank, alpha=alpha, dtype=dtype,
                                contract_ndim=contract_ndim,
                                use_bias=use_bias, name=name)
    return nn.DenseGeneral(features, axis=tuple(range(-contract_ndim, 0)),
                           use_bias=use_bias, dtype=dtype, name=name)


def lora_mask(params, extra_trainable: Sequence[str] = ("head",)):
    """Bool pytree over ``params``: True where the optimizer should update —
    adapter leaves (``lora_a``/``lora_b``) anywhere in the tree, plus every
    leaf under a top-level key in ``extra_trainable`` (the task head, by the
    same logic the CNN transfer mode trains the head over a frozen base)."""
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        trainable = (any(p in LORA_PARAM_NAMES for p in path)
                     or (path and path[0] in tuple(extra_trainable)))
        return trainable
    return walk(params, ())


def lora_optimizer(tx: optax.GradientTransformation, params=None,
                   extra_trainable: Sequence[str] = ("head",)):
    """Wrap ``tx`` so only adapter (+``extra_trainable``) leaves update —
    the ``make_optimizer(frozen_prefixes=...)`` idiom at leaf granularity.

    ``params`` may be omitted: the labels are then resolved lazily from the
    param tree at ``tx.init`` time (optax accepts a callable), which lets the
    training stack wrap the optimizer before any parameters exist — how
    ``ddw_tpu.train.lm_step`` applies the mask automatically for a model
    built with ``lora_rank > 0``."""
    def label(p):
        return jax.tree.map(lambda t: "train" if t else "frozen",
                            lora_mask(p, extra_trainable))
    labels = label(params) if params is not None else label
    return optax.multi_transform(
        {"train": tx, "frozen": optax.set_to_zero()}, labels)


def merge_base_params(lora_params, base_params, _path=""):
    """Graft a base (non-LoRA) checkpoint into a freshly initialized LoRA
    param tree: every base leaf replaces its counterpart; adapter leaves keep
    their init. Raises on a base key missing from the LoRA tree or a shape
    mismatch — a silent partial graft would fine-tune from garbage."""
    if not isinstance(base_params, dict):
        if (getattr(lora_params, "shape", None) is not None
                and lora_params.shape != base_params.shape):
            raise ValueError(f"shape mismatch at {_path!r}: "
                             f"{lora_params.shape} vs {base_params.shape}")
        return base_params
    if not isinstance(lora_params, dict):
        raise ValueError(f"base has subtree at {_path!r}, LoRA tree has leaf")
    out = dict(lora_params)
    for k, v in base_params.items():
        if k not in lora_params:
            raise ValueError(f"base key {_path + '/' + k!r} absent from the "
                             f"LoRA param tree")
        out[k] = merge_base_params(lora_params[k], v, _path + "/" + k)
    return out


def count_trainable(params, extra_trainable: Sequence[str] = ("head",)) -> tuple[int, int]:
    """(trainable, total) parameter counts under the LoRA mask — the headline
    LoRA economy number."""
    mask = lora_mask(params, extra_trainable)
    sizes = jax.tree.map(lambda a: int(jnp.size(a)), params)
    flat_m = jax.tree.leaves(mask)
    flat_s = jax.tree.leaves(sizes)
    total = sum(flat_s)
    trainable = sum(s for s, m in zip(flat_s, flat_m) if m)
    return trainable, total
