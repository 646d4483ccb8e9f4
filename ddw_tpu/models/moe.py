"""Mixture-of-Experts MLP with expert parallelism — Switch top-1 and GShard
top-2 routing.

Not a reference-parity item (the reference has no MoE — SURVEY.md §2d covers
DP/trial/HPO/batch-inference parallelism only); this is the expert-parallel
axis of the framework, same tier as TP (``parallel/sharding.py``) and SP
(``parallel/ring_attention.py``).

TPU-first formulation (Switch Transformer, Fedus et al. 2101.03961; GShard,
Lepikhin et al. 2006.16668):

- **token-choice routing** (``router="top1"`` Switch, ``router="top2"``
  GShard with renormalized pair gates) with a *static* per-expert capacity
  ``C = ceil(cf * k * T / E)`` — XLA needs fixed shapes, so routing builds
  dense dispatch/combine tensors ``[T, E, C]`` instead of data-dependent
  gathers; tokens past capacity fall through the residual connection
  (standard Switch/GShard semantics, first choices claiming capacity before
  second).
- **expert parallelism** over a named mesh axis: tokens stay sharded by the
  enclosing data/seq axes; each rank routes its local tokens against ALL ``E``
  experts, one ``lax.all_to_all`` ships the per-expert token blocks to the
  expert's owner rank, the owner applies its ``E_local = E / n`` expert FFNs,
  and a second ``all_to_all`` ships results back. The two all_to_alls ride ICI
  — this is THE canonical EP communication pattern.
- expert weights live as stacked tensors ``[E, D, H]`` (einsum over the expert
  dim hits the MXU batched); under EP each rank slices its own ``E_local``
  experts at apply time, so the parameter tree is identical with and without
  the axis (checkpoints are layout-stable; pair with ZeRO-1
  (``parallel/zero.py``) to shard the optimizer moments).
- the Switch **load-balance auxiliary loss** ``E * Σ_e f_e · p_e`` is sown
  under ``("intermediates", "moe_aux_loss")``; the LM train step adds it with
  coefficient ``aux_loss_weight`` when the model routes.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size


def collect_sown(mods: dict, name: str) -> list:
    """Every value sown under ``name`` anywhere in an ``intermediates``
    collection (flax stores sows as tuples). MoE blocks sow several keys
    (aux loss, routing telemetry, raw gate logits) — consumers MUST select by
    name rather than summing all leaves, or telemetry leaks into the loss."""
    from flax import traverse_util

    flat = traverse_util.flatten_dict(mods.get("intermediates", mods))
    return [x for path, leaf in flat.items() if name in path
            for x in (leaf if isinstance(leaf, (tuple, list)) else (leaf,))]


def top1_routing(gate_logits: jnp.ndarray, capacity: int):
    """Switch top-1 routing with static capacity.

    ``gate_logits`` [T, E] (f32) -> (dispatch [T, E, C] one-hot, combine
    [T, E, C] gate-weighted, aux_loss scalar, stats dict). Tokens beyond an
    expert's capacity get an all-zero dispatch row (they skip the expert; the
    caller's residual carries them).

    ``stats`` telemetry (all scalars except ``expert_frac`` [E]):
    ``drop_rate`` — fraction of tokens past capacity; ``balance_entropy`` —
    entropy of the expert-assignment distribution normalized by ``log E``
    (1.0 = perfectly balanced, 0.0 = collapsed onto one expert).
    """
    t, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)              # [T, E]
    expert_idx = jnp.argmax(probs, axis=-1)                   # [T]
    onehot = jax.nn.one_hot(expert_idx, e, dtype=probs.dtype)  # [T, E]
    gate = jnp.sum(probs * onehot, axis=-1)                   # [T]

    # Position of each token in its chosen expert's queue (arrival order).
    pos_in_expert = jnp.sum((jnp.cumsum(onehot, axis=0) - 1.0) * onehot,
                            axis=-1)                          # [T]
    keep = pos_in_expert < capacity
    cap_oh = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), capacity,
                            dtype=probs.dtype)                # [T, C]
    dispatch = (onehot * keep[:, None])[:, :, None] * cap_oh[:, None, :]
    combine = dispatch * gate[:, None, None]

    # Switch aux loss: E * sum_e (fraction of tokens to e) * (mean prob of e).
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    stats = {
        "drop_rate": 1.0 - jnp.mean(keep.astype(probs.dtype)),
        "balance_entropy": (-jnp.sum(frac * jnp.log(frac + 1e-9))
                            / jnp.log(float(e))),
        "expert_frac": frac,
    }
    return dispatch, combine, aux, stats


def top2_routing(gate_logits: jnp.ndarray, capacity: int):
    """GShard-style top-2 routing with static capacity (Lepikhin et al.
    2006.16668): each token dispatches to its two highest-probability experts
    with gates renormalized over the pair; first choices claim expert
    capacity before second choices (arrival order within each choice).
    Same ``[T, E, C]`` dispatch/combine contract as :func:`top1_routing`, so
    the expert-parallel all_to_all path is identical.

    Aux loss is the GShard/Switch form over FIRST-choice assignments
    (``E * Σ_e f_e · p_e``). ``drop_rate`` counts dropped (token, choice)
    slots over ``2T``; ``balance_entropy`` is over the combined assignment
    distribution of both choices.
    """
    t, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits, axis=-1)              # [T, E]
    top_p, top_i = lax.top_k(probs, 2)                        # [T, 2]
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renormalized

    dispatch = jnp.zeros((t, e, capacity), probs.dtype)
    combine = jnp.zeros((t, e, capacity), probs.dtype)
    counts = jnp.zeros((e,), probs.dtype)   # capacity already claimed
    kept_slots = 0.0
    assign_frac = jnp.zeros((e,), probs.dtype)
    for choice in range(2):
        onehot = jax.nn.one_hot(top_i[:, choice], e, dtype=probs.dtype)
        # queue position among THIS choice's tokens, offset by earlier choices
        pos = (jnp.sum((jnp.cumsum(onehot, axis=0) - 1.0) * onehot, axis=-1)
               + onehot @ counts)                             # [T]
        keep = pos < capacity
        cap_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                                dtype=probs.dtype)
        d_c = (onehot * keep[:, None])[:, :, None] * cap_oh[:, None, :]
        dispatch = dispatch + d_c
        combine = combine + d_c * gates[:, choice][:, None, None]
        counts = counts + jnp.sum(onehot, axis=0)
        kept_slots = kept_slots + jnp.sum(keep.astype(probs.dtype))
        assign_frac = assign_frac + jnp.mean(onehot, axis=0) / 2.0

    first_frac = jnp.mean(jax.nn.one_hot(top_i[:, 0], e, dtype=probs.dtype),
                          axis=0)
    aux = e * jnp.sum(first_frac * jnp.mean(probs, axis=0))
    stats = {
        "drop_rate": 1.0 - kept_slots / (2.0 * t),
        "balance_entropy": (-jnp.sum(assign_frac * jnp.log(assign_frac + 1e-9))
                            / jnp.log(float(e))),
        "expert_frac": assign_frac,
    }
    return dispatch, combine, aux, stats


def router_fn(router: str):
    """(routing fn, choices-per-token k) for a router name — the one place
    that maps names to semantics (MoEMlp and the characterization sweep both
    resolve through it, so they cannot diverge)."""
    if router == "top1":
        return top1_routing, 1
    if router == "top2":
        return top2_routing, 2
    raise ValueError(f"unknown router {router!r}; use 'top1' or 'top2'")


def expert_capacity(cf: float, k: int, tokens: int, experts: int) -> int:
    """Static per-expert capacity ``ceil(cf * k * T / E)`` (>= 1)."""
    return max(1, int(-(-cf * k * tokens // experts)))


class MoEMlp(nn.Module):
    """Drop-in MoE replacement for a transformer's dense MLP block.

    ``expert_axis=None``: every expert computed locally (dense MoE).
    ``expert_axis='data'`` (inside shard_map): expert parallelism — experts
    partitioned across the axis, tokens exchanged via ``lax.all_to_all``. The
    axis size must divide ``num_experts``.
    """

    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    expert_axis: str | None = None
    no_drop: bool = False    # inference/decode: capacity = T, never drop — a
                             # generated continuation must not depend on which
                             # other batch entries route to the same expert
    router: str = "top1"     # "top1" (Switch) or "top2" (GShard, renormalized
                             # pair gates; cf is per-choice, so effective
                             # capacity doubles relative to top1 at equal cf)

    @nn.compact
    def __call__(self, x):
        route, k = router_fn(self.router)
        b, s, d = x.shape
        t = b * s
        e = self.num_experts
        if k > e:
            raise ValueError(f"{self.router} routing needs at least {k} "
                             f"experts, got {e}")
        xt = x.reshape(t, d)

        gate_logits = nn.Dense(e, dtype=jnp.float32, name="gate")(
            xt.astype(jnp.float32))
        capacity = (t if self.no_drop
                    else expert_capacity(self.capacity_factor, k, t, e))
        dispatch, combine, aux, stats = route(gate_logits, capacity)
        self.sow("intermediates", "moe_aux_loss", aux)
        # Routing telemetry for characterization (tools/moe_capacity_sweep.py)
        # and observability; reductions over these are cheap next to the FFNs.
        self.sow("intermediates", "moe_drop_rate", stats["drop_rate"])
        self.sow("intermediates", "moe_balance_entropy",
                 stats["balance_entropy"])
        # Raw router scores for offline capacity sweeps; unused sows are
        # dead-code-eliminated by XLA in training steps.
        self.sow("intermediates", "gate_logits", gate_logits)

        # Stacked expert weights: one batched einsum per matmul (MXU-friendly),
        # identical param layout with and without EP.
        k_init = nn.initializers.lecun_normal()
        w1 = self.param("w1", k_init, (e, d, self.mlp_dim), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (e, self.mlp_dim),
                        jnp.float32)
        w2 = self.param("w2", k_init, (e, self.mlp_dim, d), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (e, d), jnp.float32)

        # [T, E, C] x [T, D] -> per-expert token blocks [E, C, D]
        expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(self.dtype),
                               xt.astype(self.dtype))

        def ffn(blocks, w1_, b1_, w2_, b2_):
            # blocks [..., E?, C', D] with matching leading expert dim in w/b
            h = jnp.einsum("...ecd,edh->...ech", blocks,
                           w1_.astype(self.dtype))
            h = nn.gelu(h + b1_.astype(self.dtype)[..., None, :])
            out = jnp.einsum("...ech,ehd->...ecd", h, w2_.astype(self.dtype))
            return out + b2_.astype(self.dtype)[..., None, :]

        if self.expert_axis is None:
            expert_out = ffn(expert_in, w1, b1, w2, b2)        # [E, C, D]
        else:
            n = axis_size(self.expert_axis)
            if e % n:
                raise ValueError(f"num_experts {e} not divisible by "
                                 f"{self.expert_axis!r} axis size {n}")
            e_local = e // n
            me = lax.axis_index(self.expert_axis)
            # Ship each expert's token block to its owner rank: regroup the
            # expert dim by owner, all_to_all over the owner dim. Result on
            # rank r: [n_src, E_local, C, D] — r's experts' tokens from every
            # source rank.
            grouped = expert_in.reshape(n, e_local, capacity, d)
            received = lax.all_to_all(grouped, self.expert_axis,
                                      split_axis=0, concat_axis=0, tiled=False)
            sl = lambda p: lax.dynamic_slice_in_dim(  # noqa: E731
                p, me * e_local, e_local, axis=0)
            out_blocks = ffn(received, sl(w1), sl(b1), sl(w2), sl(b2))
            # Inverse exchange: results back to the tokens' source ranks.
            returned = lax.all_to_all(out_blocks, self.expert_axis,
                                      split_axis=0, concat_axis=0, tiled=False)
            expert_out = returned.reshape(e, capacity, d)

        out = jnp.einsum("tec,ecd->td", combine.astype(self.dtype),
                         expert_out)
        return out.reshape(b, s, d)
