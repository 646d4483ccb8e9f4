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

One pass scores and chooses, by tiles of queries (``indexer``, ``key_select``
scopes; plain XLA, exact top-k without a sort) and keeps a byte a pair. A
second attends under that choice and forms the KL term (``sparse_attention``),
on one of two tiers that ``impl`` names and ``auto`` picks from the shapes:

- ``pallas``: the kernels of :mod:`ddw_tpu.ops.indexed_kernels`. The choice
  goes in as a mask shared by all heads, every score tile stays in VMEM, K and
  V are read once a group of query heads, and a pass of a forward's shape
  hands back the heads' summed probabilities ``[B, S, S]`` float32, the
  indexer's target. Whole query groups of head dim 64 or 128 over a sequence
  of at least 512 that the kernels' blocks divide;
- ``xla``: the tiles the kernels replaced, each rematerialised in the
  backward pass, which compute the float32 score of every key a tile's group
  of queries can see and mask: every other shape, and the kernels' test
  oracle.

Either way the KL term is XLA's, by the choose pass's tiles: the index scores
at full precision, their log-softmax over the chosen keys, the sum under the
mask. The tiles make the scores again, forward and, rematerialised, backward;
beside the kernels the forward pass reads the scores the choice was made from
and only the backward pass multiplies (``_kl_tile_scored``). Scores, the choice
and the loss are float32.
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


def _groups(s: int, tile: int):
    """Tiles go in up to four groups, each against the keys its last query can
    see and no more (5/8 of the pairs at four groups): ``(first row, keys
    seen)`` a group."""
    n_tiles = s // tile
    groups = 4 if n_tiles % 4 == 0 else 1
    rows = n_tiles // groups * tile
    return [(g * rows, (g + 1) * rows) for g in range(groups)]


def _tiles(x, first: int, last: int, tile: int):
    """Rows ``[first, last)`` of ``x`` as ``[tiles, tile, ...]``."""
    return x[first:last].reshape(-1, tile, *x.shape[1:])


def _choose_row(qi, ki, wi, topk: int, tile: int):
    """One sequence's choice, a bool ``[tiles, tile, seen_keys]`` a group,
    and the index scores it was made from, float32 of the same shape (a
    caller that does not read them does not pay for them)."""
    qi, ki, wi = (lax.stop_gradient(x) for x in (qi, ki, wi))
    masks, scored = [], []
    for first, seen_keys in _groups(qi.shape[0], tile):
        pos = jnp.arange(seen_keys)
        starts = first + jnp.arange((seen_keys - first) // tile) * tile

        def choose(args, seen_keys=seen_keys, pos=pos):
            qi_t, wi_t, start = args
            with jax.named_scope("indexer"):
                scores = index_scores(qi_t, ki[:seen_keys], wi_t)
            with jax.named_scope("key_select"):
                seen = pos[None, :] <= (start + jnp.arange(tile))[:, None]
                return (seen & top_mask(jnp.where(seen, scores, -jnp.inf),
                                        topk), scores)

        mask, scores = lax.map(choose, (_tiles(qi, first, seen_keys, tile),
                                        _tiles(wi, first, seen_keys, tile),
                                        starts))
        # a block rematerialised whole keeps the choice and what came of it,
        # and makes neither twice
        masks.append(checkpoint_name(mask, "key_mask"))
        scored.append(scores)
    return tuple(masks), tuple(scored)


def _whole(masks, s: int):
    """The groups' masks ``[..., tiles, tile, seen_keys]`` as one ``[..., S,
    S]``."""
    def pad(m):
        m = m.reshape(*m.shape[:-3], -1, m.shape[-1])
        return jnp.pad(m, [(0, 0)] * (m.ndim - 1) + [(0, s - m.shape[-1])])
    return jnp.concatenate([pad(m) for m in masks], axis=-2)


def _kl_of(scores, target, mask):
    """A tile of queries' KL terms from the heads' summed probabilities
    ``target [tile, seen_keys]`` (a constant to the gradient, read under the
    mask) and the indexer's own distribution over the chosen keys, the
    softmax of its ``scores`` there."""
    target = lax.stop_gradient(jnp.where(mask, target, 0.0))
    log_pi = jax.nn.log_softmax(jnp.where(mask, scores, MASKED), axis=-1)
    log_target = jnp.log(jnp.where(target > 0, target, 1.0))
    return jnp.sum(jnp.where(mask, target * (log_target - log_pi), 0.0),
                   axis=-1)


def _kl_tile(target, mask, qi_t, ki_g, wi_t):
    return _kl_of(index_scores(qi_t, ki_g, wi_t), target, mask)


@jax.custom_vjp
def _kl_tile_scored(scores, target, mask, qi_t, ki_g, wi_t):
    """:func:`_kl_tile` with the choose pass's ``scores`` of the same three
    inputs at hand: the forward pass multiplies nothing; the backward pass
    makes the index scores again, with their gradient — what a tile
    rematerialised costs, less its forward."""
    return _kl_of(scores, target, mask)


def _kl_tile_scored_fwd(scores, target, mask, qi_t, ki_g, wi_t):
    return _kl_of(scores, target, mask), (target, mask, qi_t, ki_g, wi_t)


def _kl_tile_scored_bwd(residuals, g):
    target, mask, *inputs = residuals
    _, vjp = jax.vjp(lambda *a: _kl_tile(target, mask, *a), *inputs)
    return (None, None, None, *vjp(g))


_kl_tile_scored.defvjp(_kl_tile_scored_fwd, _kl_tile_scored_bwd)


def _row_xla(q, k, v, qi, ki, wi, masks, tile: int):
    """One sequence on the XLA tiles. ``q [S, H, D]``, ``k, v [S, KV, D]``,
    ``qi [S, J, Di]``, ``ki [S, Di]``, ``wi [S, J]`` and the choice ->
    ``out [S, H, D]``, ``kl [S]``."""
    s, h, d = q.shape
    kv = k.shape[1]

    def attend_group(first, seen_keys, masks):
        k_g, v_g, ki_g = k[:seen_keys], v[:seen_keys], ki[:seen_keys]

        @jax.checkpoint
        def attend(q_t, qi_t, wi_t, mask):
            scores = jnp.einsum(
                "qhgd,khd->hgqk", q_t.reshape(tile, kv, h // kv, d), k_g,
                preferred_element_type=jnp.float32) * (float(d) ** -0.5)
            probs = _softmax(jnp.where(mask, scores, MASKED))
            out = jnp.einsum("hgqk,khd->qhgd", probs.astype(v.dtype), v_g)
            # the indexer's target: the heads' probabilities summed and
            # L1-normalised
            kl = _kl_tile(jnp.sum(probs, axis=(0, 1)) / h, mask, qi_t, ki_g,
                          wi_t)
            return out.reshape(tile, h, d), kl

        cut = lambda x: _tiles(x, first, seen_keys, tile)  # noqa: E731
        out, kl = lax.map(lambda a: attend(*a),
                          (cut(q), cut(qi), cut(wi), masks))
        return out.reshape(-1, h, d), kl.reshape(-1)

    out, kl = (jnp.concatenate(x) for x in zip(*(
        attend_group(*g, m) for g, m in zip(_groups(s, tile), masks))))
    return out, kl


def _row_kl(target, qi, ki, wi, masks, scored, tile: int):
    """One sequence's KL terms ``[S]`` from the kernels' target ``[S, S]``,
    by the tiles and groups of the choice and from its scores."""
    def group(first, seen_keys, masks, scores):
        ki_g = ki[:seen_keys]
        cut = lambda x: _tiles(x, first, seen_keys, tile)  # noqa: E731
        return lax.map(
            lambda a: _kl_tile_scored(a[0], a[1], a[2], a[3], ki_g, a[4]),
            (scores, cut(target[:, :seen_keys]), masks, cut(qi), cut(wi))
        ).reshape(-1)

    return jnp.concatenate([group(*g, m, sc) for g, m, sc in
                            zip(_groups(qi.shape[0], tile), masks, scored)])


def _tier(q, k, impl: str) -> str:
    """``impl``, or for ``auto`` what the shapes say: the kernels for whole
    query groups of head dim 64 or 128 over a sequence of at least 512 that
    their blocks divide, the XLA tiles for every other."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown impl {impl!r}; use 'auto', 'pallas' or "
                         f"'xla'")
    if impl != "auto":
        return impl
    from ddw_tpu.ops import indexed_kernels as ik

    (_, s, h, d), kv = q.shape, k.shape[2]
    fits = (s >= ik.MIN_SEQ and ik.pick_blocks(s) and d in ik.HEAD_DIMS
            and h % kv == 0)
    return "pallas" if fits else "xla"


def indexed_attention(q, k, v, qi, ki, wi, *, topk: int, tile: int = 512,
                      impl: str = "auto"):
    """``q [B, S, H, D]``, ``k, v [B, S, KV, D]`` (rotated, in the compute
    dtype); the indexer's ``qi [B, S, J, Di]``, ``ki [B, S, Di]``,
    ``wi [B, S, J]`` (float32, rotated). Returns the attention output
    ``[B, S, H, D]``, each query's KL term ``[B, S]`` — gradient to the
    indexer's three inputs only — the number of keys it chose ``[B, S]`` and
    the choice ``[B, S, S]`` (one byte a pair; a caller that does not read it
    does not pay for it). ``tile`` queries are scored at a time; a sequence
    it does not divide goes as one tile. Rows are scored one after the other:
    a tile's float32 index scores over all heads are the path's peak memory.
    ``impl``: ``auto`` (by the shapes, :func:`_tier`), ``pallas`` (the
    kernels, any sequence their blocks divide) or ``xla`` (the tiles)."""
    s = q.shape[1]
    tile = tile if s % tile == 0 else s
    masks, scored = lax.map(lambda a: _choose_row(*a, topk=topk, tile=tile),
                            (qi, ki, wi))
    choice = _whole(masks, s)
    with jax.named_scope("sparse_attention"):
        if _tier(q, k, impl) == "pallas":
            from ddw_tpu.ops.indexed_kernels import attend_chosen

            out, target = attend_chosen(q, k, v, choice.astype(jnp.int8))
            kl = lax.map(lambda a: _row_kl(*a, tile=tile),
                         (target, qi, ki, wi, masks, scored))
        else:
            out, kl = lax.map(lambda a: _row_xla(*a, tile=tile),
                              (q, k, v, qi, ki, wi, masks))
    return (checkpoint_name(out, "attention_out"),
            checkpoint_name(kl, "attention_out"),
            jnp.sum(choice, axis=-1), choice)
