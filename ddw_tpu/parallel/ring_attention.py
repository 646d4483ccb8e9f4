"""Ring attention — sequence/context parallelism over a ``seq`` mesh axis.

Not in the reference (its workload is a CNN; SURVEY.md §2d marks SP "not required
for parity"), but long-context is first-class here: this is the component that
lets attention scale past one device's memory by sharding the *sequence* axis.

Algorithm (Liu et al. 2023, blockwise ring attention): each of the N devices on
the ``seq`` axis holds Q/K/V shards of S/N tokens. Q stays put; K/V shards rotate
around the ring N times via ``ppermute`` (ICI neighbor exchange). Each hop, every
device runs the Pallas flash kernel (:func:`ddw_tpu.ops.flash_attention
.flash_attention_lse`) on its local Q against the visiting K/V block — O(S_local)
VMEM, the S_local x S_local score matrix never exists even per hop — and folds
the hop's (out, logsumexp) into a running softmax combine, the same online
softmax as inside the kernel, lifted to the ring level. Communication overlaps
compute under XLA's scheduler; per-hop cost is one flash call plus one neighbor
exchange.

Causal masking works on *global* positions, resolved per hop into one of three
static cases (the visiting block's offset relative to ours is ``me - hop``):
  - hop 0: the diagonal block -> causal flash with equal offsets;
  - visiting block strictly in the past (``hop <= me``) -> full (non-causal)
    flash, no mask;
  - visiting block strictly in the future -> fully masked; the hop is SKIPPED
    via ``lax.cond`` (the old einsum formulation paid full price to multiply
    by an all -inf mask).
This keeps the kernel's offsets static (Pallas grid masking needs Python ints)
while the rank-dependent choice stays dynamic.

Gradient path: ``flash_attention_lse``'s custom VJP carries cotangents for both
the output and the logsumexp, so the cross-hop combine backpropagates exactly
(the hop-vs-full equivalence test pins fwd AND grads). Residual memory is the
per-hop K/V copies (O(S_global) across hops per device — same as the forward
K/V rotation); the S^2 matrices never exist in any pass.

Use under ``shard_map`` with in_specs splitting the sequence dim over ``seq``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

from ddw_tpu.ops.flash_attention import flash_mha_lse

_NEG_INF = -1e30


def _combine(o1, lse1, o2, lse2):
    """Softmax-combine two partial attentions over disjoint key sets.

    Each o_i is normalized over its own keys with logsumexp lse_i; the combined
    result over the union is a convex combination weighted by exp(lse_i - lse).
    Safe at lse = -inf sentinels: logaddexp keeps the max's scale, weights stay
    finite, and an all-masked row yields the zero vector."""
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse)[..., None]
    w2 = jnp.exp(lse2 - lse)[..., None]
    return o1 * w1 + o2 * w2, lse


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: float | None = None,
                   block_q: int | None = None, block_k: int | None = None,
                   impl: str = "auto") -> jnp.ndarray:
    """Blockwise ring attention over ``axis_name``.

    Per-device shapes: q/k/v [B, H, S_local, D] (the local sequence shard);
    returns the local shard of the attention output. Must be called inside
    ``shard_map``/``pmap`` binding ``axis_name``. ``impl`` selects the per-hop
    attention arm (``auto``/``xla``/``xla_ckpt``/``pallas``/``pallas_short``
    — see :func:`ddw_tpu.ops.flash_attention.flash_mha_lse`): auto picks by
    the LOCAL shard's shape, so short shards get the fused XLA arm, shards of
    512 tokens and more the streaming flash kernels and those between 192
    and 512 the one-block kernels.
    """
    n = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(d) ** 0.5

    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur = k, v

    # Running combined (out f32, lse f32) over ring hops.
    out = jnp.zeros((b, h, s_local, d), jnp.float32)
    lse = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)

    def flash(k_hop, v_hop, hop_causal):
        # flash_mha_lse pads non-tile-multiple s_local internally, so any
        # shard length works (parity with the einsum formulation it replaced).
        o, l = flash_mha_lse(q, k_hop, v_hop, hop_causal, sm_scale,
                             block_q, block_k, impl=impl)
        return o.astype(jnp.float32), l

    for hop in range(n):
        # Visiting block is rank (me - hop) % n's shard. Relative position in
        # the global order: hop 0 = our own (diagonal), otherwise strictly past
        # iff hop <= me, strictly future iff hop > me.
        if causal and hop == 0:
            o_h, lse_h = flash(k_cur, v_cur, True)
            out, lse = _combine(out, lse, o_h, lse_h)
        elif causal:
            def _attend(args):
                out, lse, k_hop, v_hop = args
                o_h, lse_h = flash(k_hop, v_hop, False)
                return _combine(out, lse, o_h, lse_h)

            def _skip(args):
                out, lse, _, _ = args
                return out, lse

            out, lse = lax.cond(hop <= me, _attend, _skip,
                                (out, lse, k_cur, v_cur))
        else:
            o_h, lse_h = flash(k_cur, v_cur, False)
            out, lse = _combine(out, lse, o_h, lse_h)
        if hop != n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)

    return out.astype(q.dtype)
