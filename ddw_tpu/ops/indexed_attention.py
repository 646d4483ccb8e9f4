"""Attention over keys an indexer chose: the training path.

A lightning indexer (the sparse-attention scheme of DeepSeek-V3.2's technical
report, carried here to a GQA model) gives every (query, key) pair a cheap
score from a few small heads,

    I[t, s] = J^-1/2 * Di^-1/2 * sum_j w[t, j] * relu(qI[t, j] . kI[s]),

and each query then attends to the ``topk`` causal keys of largest score, the
same set for all of its heads (every causal key while it has fewer). The
indexer learns from a KL loss against the main attention's own probabilities,
summed over heads on the chosen set, so this path returns that loss beside the
output. The choice itself is not differentiated.

Plain XLA, by tiles of queries so that no ``[S, S]`` tensor per head is ever
whole in memory: one pass scores and chooses (``indexer``, ``key_select``
scopes), a second, rematerialised tile by tile in the backward pass, attends
under the chosen mask (``sparse_attention``). It computes the score of every
key a tile's group of queries can see and masks, about ``S / topk`` times the
required work at long ``S``; a kernel that gathers is the next step
(PERF.md). Scores, the choice and the loss are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

MASKED = -1e30      # finite: a masked score never meets an infinity


def index_scores(qi, ki, wi):
    """``qi [q, J, Di]``, ``ki [S, Di]``, ``wi [q, J]`` -> ``I [q, S]``."""
    j, di = qi.shape[-2:]
    dots = jnp.einsum("qjd,kd->jqk", qi, ki, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    return (float(j) ** -0.5 * float(di) ** -0.5) * jnp.sum(
        wi.T[:, :, None] * jax.nn.relu(dots), axis=0)


def top_mask(x, k: int):
    """``x [..., n]`` float32 -> bool mask of each row's ``k`` largest entries
    (all of them where ``n <= k``); among equal values the earlier entry
    wins, as ``lax.top_k`` has it. Exact, and no sort: the ``k``-th largest
    value's 32 bits are found one at a time, most significant first, by
    counting on keys whose unsigned order is the floats' order, and then, the
    same way, the index up to which entries equal to it still fit."""
    n = x.shape[-1]
    x = jnp.where(x == 0, 0.0, x)          # -0.0 and 0.0 are one value
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def largest_with(count_at_least, steps):
        """The largest ``steps``-bit number ``c`` with ``count_at_least(c)``
        (a predicate that holds from 0 up to some number)."""
        def bit(i, cur):
            cand = cur | (jnp.uint32(1) << (steps - 1 - i).astype(jnp.uint32))
            return jnp.where(count_at_least(cand), cand, cur)
        return lax.fori_loop(0, steps, bit,
                             jnp.zeros(x.shape[:-1], jnp.uint32))

    count = lambda hit: jnp.sum(hit, axis=-1, dtype=jnp.int32)  # noqa: E731
    kth = largest_with(lambda c: count(keys >= c[..., None]) >= k, 32)[..., None]
    above, tied = keys > kth, keys == kth
    room = k - count(above)                 # ties that still fit
    index = jnp.arange(n, dtype=jnp.uint32)
    # the largest index bound under which fewer than ``room`` ties lie; the
    # ties up to and including it are the ``room`` earliest
    bound = largest_with(
        lambda c: count(tied & (index < c[..., None])) < room,
        max(1, (n - 1).bit_length()))[..., None]
    return above | (tied & (index <= bound) & (room > 0)[..., None])


def _softmax(scores):
    """Softmax over the last axis. The barrier keeps XLA from fusing the
    maximum, the exponential and the sum into one reduce-window fusion, which
    it does in a forward-only program and which runs seven times slower than
    the three apart (v5e, a ``[32, 512, 8192]`` tile: 59 ms against 8)."""
    top = lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    scores, top = lax.optimization_barrier((scores, top))
    e = jnp.exp(scores - top)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _row(q, k, v, qi, ki, wi, topk: int, tile: int):
    """One sequence. ``q [S, H, D]``, ``k, v [S, KV, D]``, ``qi [S, J, Di]``,
    ``ki [S, Di]``, ``wi [S, J]`` -> ``out [S, H, D]``, ``kl [S]``,
    ``chosen [S]`` and the choice itself ``[S, S]``."""
    s, h, d = q.shape
    kv = k.shape[1]
    n_tiles = s // tile
    # tiles go in up to four groups, each against the keys its last query can
    # see and no more: 5/8 of the pairs at four groups
    groups = 4 if n_tiles % 4 == 0 else 1
    per = n_tiles // groups
    ki_fixed = lax.stop_gradient(ki)

    def attend_group(g):
        seen_keys = (g + 1) * per * tile
        rows = slice(g * per * tile, seen_keys)
        cut = lambda x: x[rows].reshape(per, tile, *x.shape[1:])  # noqa: E731
        k_g, v_g, ki_g = k[:seen_keys], v[:seen_keys], ki[:seen_keys]
        pos = jnp.arange(seen_keys)
        starts = (g * per + jnp.arange(per)) * tile

        def choose(args):
            qi_t, wi_t, start = args
            with jax.named_scope("indexer"):
                scores = index_scores(qi_t, ki_fixed[:seen_keys], wi_t)
            with jax.named_scope("key_select"):
                seen = pos[None, :] <= (start + jnp.arange(tile))[:, None]
                return seen & top_mask(jnp.where(seen, scores, -jnp.inf), topk)

        masks = lax.map(choose, (lax.stop_gradient(cut(qi)),
                                 lax.stop_gradient(cut(wi)), starts))
        # a block rematerialised whole keeps the choice and what came of it,
        # and makes neither twice
        masks = checkpoint_name(masks, "key_mask")

        @jax.checkpoint
        def attend(q_t, qi_t, wi_t, mask):
            with jax.named_scope("sparse_attention"):
                scores = jnp.einsum(
                    "qhgd,khd->hgqk", q_t.reshape(tile, kv, h // kv, d), k_g,
                    preferred_element_type=jnp.float32) * (float(d) ** -0.5)
                probs = _softmax(jnp.where(mask, scores, MASKED))
                out = jnp.einsum("hgqk,khd->qhgd", probs.astype(v.dtype), v_g)
                # the indexer's target: the heads' probabilities summed and
                # L1-normalised, a constant to the gradient
                target = lax.stop_gradient(jnp.sum(probs, axis=(0, 1)) / h)
                log_pi = jax.nn.log_softmax(jnp.where(
                    mask, index_scores(qi_t, ki_g, wi_t), MASKED), axis=-1)
                log_target = jnp.log(jnp.where(target > 0, target, 1.0))
                kl = jnp.sum(
                    jnp.where(mask, target * (log_target - log_pi), 0.0),
                    axis=-1)
            return out.reshape(tile, h, d), kl

        out, kl = lax.map(lambda a: attend(*a),
                          (cut(q), cut(qi), cut(wi), masks))
        whole = jnp.pad(masks.reshape(per * tile, seen_keys),
                        ((0, 0), (0, s - seen_keys)))
        return out.reshape(per * tile, h, d), kl.reshape(per * tile), whole

    out, kl, choice = (jnp.concatenate(x) for x in
                       zip(*(attend_group(g) for g in range(groups))))
    return (checkpoint_name(out, "attention_out"),
            checkpoint_name(kl, "attention_out"),
            jnp.sum(choice, axis=-1), choice)


def indexed_attention(q, k, v, qi, ki, wi, *, topk: int, tile: int = 512):
    """``q [B, S, H, D]``, ``k, v [B, S, KV, D]`` (rotated, in the compute
    dtype); the indexer's ``qi [B, S, J, Di]``, ``ki [B, S, Di]``,
    ``wi [B, S, J]`` (float32, rotated). Returns the attention output
    ``[B, S, H, D]``, each query's KL term ``[B, S]`` — gradient to the
    indexer's three inputs only — the number of keys it chose ``[B, S]`` and
    the choice ``[B, S, S]`` (one byte a pair; a caller that does not read it
    does not pay for it). ``tile`` queries are scored at a time; a sequence
    it does not divide goes as one tile. Rows go one after the other: a
    tile's float32 scores over all heads are the path's peak memory."""
    s = q.shape[1]
    tile = tile if s % tile == 0 else s
    return lax.map(lambda a: _row(*a, topk=topk, tile=tile),
                   (q, k, v, qi, ki, wi))
