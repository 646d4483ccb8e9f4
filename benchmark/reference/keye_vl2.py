"""The language decoder of Keye-VL-2.0-30B-A3B as plain ``jax.numpy``: forward,
the two losses and, through ``jax.grad``, their gradients. Float32,
``Precision.HIGHEST``, no kernels, no cache, nothing of the program imported.
Written from the layer's equations (ISSUE 32, section 1; PERF.md section 4),
with the configuration file's keys (``configs/keye-vl-2.0-30b-a3b.json``).

A layer, pre-norm residual on ``h``:

    x  = RMSNorm(h);  q, k, v = x Wq, x Wk, x Wv  (32 / 4 / 4 heads of 128)
    q, k <- RMSNorm over each head's 128;  M-RoPE (sections 16/24/24, text
            positions (t, t, t): plain RoPE on interleaved pairs)
    indexer, on xb = stop_gradient(x):
        qI = xb WqI (16 heads of 64), kI = LayerNorm(xb WkI), w = xb Ww
        RoPE on qI, kI;  I[t,s] = 16^-1/2 64^-1/2 sum_j w[t,j] relu(qI[t,j].kI[s])
        S_t = the 2,048 causal keys of largest I, the earlier key where two
              are equal (all of them while t < 2,048)
    o_t = sum_{s in S_t} softmax_{S_t}(q_t.k_s / sqrt(128)) v_s, eight query
          heads a key head;  h <- h + o Wo
    x2 = RMSNorm(h);  p = softmax(x2 Wr) over all 128 experts; the 8 largest,
          renormalised;  h <- h + sum_{e in top8, held here} w_e E_e(x2),
          E_e(x) = (silu(x Wgate_e) * (x Wup_e)) Wdown_e
    L_I adds mean_t KL(phat_t || softmax_{S_t} I[t,.]), phat_t the main
          attention's probabilities summed over the 32 heads on S_t,
          L1-normalised, a constant to the gradient.

Only the experts held here (``num_experts`` of the router's
``deployment.published_num_experts``, from ``deployment.first_expert``) add to
the residual: the chip's share of an expert-parallel layer. Scores by blocks of
``QUERY_BLOCK`` queries so that a row of 8,192 tokens fits; layers
stacked on a leading axis and scanned, one ``jax.checkpoint`` a block.

**Choices handed in.** The two top-k choices are not continuous: a program in
bfloat16 and this float32 reference rank the 2,048th and 2,049th key of a
query, or the 8th and 9th expert of a token, the other way round now and then,
and what follows differs by far more than rounding (PERF.md section 2 has the
readings). So the comparison that decides ``correct`` can hand this reference
the choices the program made (``attach_choices`` packs them behind the token
ids of each row; ``make_loss`` finds them there), and the rest is compared
with the choices equal. ``choice_margins`` then holds every choice handed in
against this reference's own scores: how far, at worst, a chosen item lies
below the reference's own k-th score, or an item left out above it, and what
share of the chosen items the reference itself did not choose. ``own_choices``
gives the reference's own choices, sound or with a fault planted (scores in a
lower precision, the choice off by ``shift`` ranks): the controls those limits
are set against (``tools/choice_faults.py``).

``embedding_multiplier`` (a key of the configuration, 1 without): the token
embeddings are multiplied by it, as the original Transformer's and Gemma's
are by sqrt(hidden). The benchmark's seeded table is N(0, 0.02) like every
matrix; without the multiplier the blocks' first outputs, whose mean over the
keys is common to all queries, are larger than the embeddings, the residual
stream is the same vector at every token from the second layer on, and every
token routes to the same experts (PERF.md section 6).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.matmul import make_einsum

MASKED = -1e30
QUERY_BLOCK = 128   # queries scored at a time: a block's float32 scores over
                    # the 32 heads and a row's 8,192 keys are 134 MB, and the
                    # backward pass holds a few; no result depends on it


def sizes_of(c: dict) -> dict:
    sa, dep = c["sa_config"], c["deployment"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "kv": c["num_key_value_heads"], "hd": c["head_dim"],
            "f": c["moe_intermediate_size"], "held": c["num_experts"],
            "width": dep["published_num_experts"],
            "first": dep["first_expert"], "k": c["num_experts_per_tok"],
            "v": c["vocab_size"], "n": c["num_hidden_layers"],
            "j": sa["indexer_num_heads"], "di": sa["indexer_head_dim"],
            "topk": sa["topk"], "tile": QUERY_BLOCK,
            "eps": c["rms_norm_eps"], "theta": float(c["rope_theta"])}


def weight_spec(c: dict) -> dict:
    z = sizes_of(c)
    d, hd = z["d"], z["hd"]
    spec = {"wte": ((z["v"], d), "w"), "lnf.g": ((d,), "gain"),
            "head.w": ((d, z["v"]), "w")}
    for name, shape, kind in (
            ("ln1.g", (d,), "gain"),
            ("attn.wq", (d, z["h"] * hd), "w"),
            ("attn.wk", (d, z["kv"] * hd), "w"),
            ("attn.wv", (d, z["kv"] * hd), "w"),
            ("attn.wo", (z["h"] * hd, d), "w"),
            ("attn.qn.g", (hd,), "gain"), ("attn.kn.g", (hd,), "gain"),
            ("idx.wq", (d, z["j"] * z["di"]), "w"),
            ("idx.wk", (d, z["di"]), "w"), ("idx.ww", (d, z["j"]), "w"),
            ("idx.kn.g", (z["di"],), "gain"), ("idx.kn.b", (z["di"],), "bias"),
            ("ln2.g", (d,), "gain"),
            ("moe.router", (d, z["width"]), "w"),
            ("moe.gate", (z["held"], d, z["f"]), "w"),
            ("moe.up", (z["held"], d, z["f"]), "w"),
            ("moe.down", (z["held"], z["f"], d), "w")):
        spec["blk." + name] = ((z["n"], *shape), kind)
    return spec


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rope(x, theta: float):
    """``x [S, heads, hd]``, positions 0..S-1, pairs ``(2i, 2i+1)`` turned by
    ``t / theta^(2i/hd)``. With the three M-RoPE components equal, as for text,
    every section turns by the same ``t``: this."""
    s, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def kth_largest(x, k: int):
    """Along the last axis; the smallest where it holds fewer than ``k``."""
    k = min(k, x.shape[-1])
    return -jnp.sort(-x, axis=-1)[..., k - 1]


def top_choice(x, k: int):
    """Bool mask of the ``k`` largest along the last axis (all, where it holds
    fewer), the earlier entry where two are equal."""
    k = min(k, x.shape[-1])
    kth = kth_largest(x, k)[..., None]
    above, tied = x > kth, x == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))


def shifted_choice(x, k: int, shift: int):
    """:func:`top_choice` with a fault planted: the ``k - shift`` largest and
    then ranks ``k + 1 .. k + shift``, so ``shift`` of ``k`` choices are wrong
    by the least distance a wrong choice can be. Sound where fewer than
    ``k + shift`` entries are there to choose from."""
    sound = top_choice(x, k)
    if not shift:
        return sound
    off = top_choice(x, k - shift) | (top_choice(x, k + shift) & ~sound)
    enough = jnp.sum(x > -jnp.inf, axis=-1, keepdims=True) >= k + shift
    return jnp.where(enough, off, sound)


def worst_misplaced(scores, chosen, allowed, kth, scale):
    """How far a chosen entry lies below ``kth``, or an allowed entry that was
    not chosen above it, at worst along the last axis, in units of
    ``scale``."""
    low = jnp.max(jnp.where(chosen, kth - scores, 0.0), axis=-1)
    high = jnp.max(jnp.where(allowed & ~chosen, scores - kth, 0.0), axis=-1)
    return jnp.maximum(low, high) / scale


def pack_bits(mask):
    """Bool ``[..., n]`` -> int32 ``[..., ceil(n / 32)]``, bit ``i`` of a word
    its ``i``-th entry."""
    n = mask.shape[-1]
    words = -(-n // 32)
    padded = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, words * 32 - n)])
    bits = padded.reshape(*mask.shape[:-1], words, 32).astype(jnp.uint32)
    packed = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                     dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


def unpack_bits(words, n: int):
    """The inverse of :func:`pack_bits`."""
    bits = (jax.lax.bitcast_convert_type(words, jnp.uint32)[..., None]
            >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(*words.shape[:-1], -1)[..., :n].astype(bool)


def attention(x, p: dict, z: dict, einsum, given=None, shift: int = 0):
    """One sequence ``x [S, d]`` (normed). Returns what the layer adds to the
    residual ``[S, d]``, the mean KL term, the mean number of chosen keys, the
    worst misplaced choice with the share of choices the reference did not
    make itself, and the keys attended to, packed. ``given``: the row's chosen
    keys, packed (``[S, ceil(S / 32)]``); the reference's own choice without,
    off by ``shift`` ranks (:func:`shifted_choice`)."""
    s = x.shape[0]
    h, kv, hd = z["h"], z["kv"], z["hd"]
    q = einsum("sd,de->se", x, p["attn.wq"]).reshape(s, h, hd)
    k = einsum("sd,de->se", x, p["attn.wk"]).reshape(s, kv, hd)
    v = einsum("sd,de->se", x, p["attn.wv"]).reshape(s, kv, hd)
    q = rope(rms_norm(q, p["attn.qn.g"], z["eps"]), z["theta"])
    k = rope(rms_norm(k, p["attn.kn.g"], z["eps"]), z["theta"])

    xb = jax.lax.stop_gradient(x)
    qi = einsum("sd,de->se", xb, p["idx.wq"]).reshape(s, z["j"], z["di"])
    ki = layer_norm(einsum("sd,de->se", xb, p["idx.wk"]), p["idx.kn.g"],
                    p["idx.kn.b"], z["eps"])
    wi = einsum("sd,dj->sj", xb, p["idx.ww"])
    qi = rope(qi, z["theta"])
    ki = rope(ki[:, None], z["theta"])[:, 0]

    tile = z["tile"] if s % z["tile"] == 0 else s
    scale_i = 1.0 / math.sqrt(z["j"]) / math.sqrt(z["di"])
    pos = jnp.arange(s)

    @jax.checkpoint
    def query_block(args):
        q_b, qi_b, wi_b, t_b, given_b = args
        index = scale_i * jnp.sum(
            wi_b[:, :, None] * jax.nn.relu(einsum("qjd,kd->qjk", qi_b, ki)),
            axis=1)
        causal = pos[None, :] <= t_b[:, None]
        masked = jax.lax.stop_gradient(jnp.where(causal, index, -jnp.inf))
        chosen = causal & shifted_choice(masked, z["topk"], shift)
        missed = jnp.zeros((tile,))
        if given is not None:
            # a choice handed in has to be a choice: as many keys, all seen
            own, chosen = chosen, unpack_bits(given_b, s)
            spread = jnp.std(index, axis=-1, where=causal) + 1e-30
            off = worst_misplaced(
                masked, chosen, causal,
                kth_largest(masked, z["topk"])[:, None], spread)
            valid = (jnp.all(causal | ~chosen, axis=-1)
                     & (jnp.sum(chosen, -1) == jnp.sum(own, -1)))
            off = jnp.where(valid, off, jnp.inf)
            missed = (jnp.sum(chosen & ~own, axis=-1)
                      / jnp.maximum(jnp.sum(own, axis=-1), 1))
        else:
            off = jnp.zeros((tile,))
        scores = einsum("qhgd,khd->hgqk", q_b.reshape(tile, kv, h // kv, hd),
                        k) / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(chosen, scores, MASKED), axis=-1)
        out = einsum("hgqk,khd->qhgd", probs, v).reshape(tile, h * hd)
        target = jax.lax.stop_gradient(jnp.sum(probs, axis=(0, 1)) / h)
        log_pi = jax.nn.log_softmax(jnp.where(chosen, index, MASKED), axis=-1)
        log_target = jnp.log(jnp.where(target > 0, target, 1.0))
        kl = jnp.sum(jnp.where(chosen, target * (log_target - log_pi), 0.0),
                     axis=-1)
        return (out, kl, jnp.sum(chosen, axis=-1), off, missed,
                pack_bits(chosen))

    cut = lambda a: a.reshape(s // tile, tile, *a.shape[1:])   # noqa: E731
    handed = cut(given if given is not None else jnp.zeros((s, 0), jnp.int32))
    out, kl, n, off, missed, keys = jax.lax.map(
        query_block, (cut(q), cut(qi), cut(wi), cut(pos), handed))
    out = einsum("se,ed->sd", out.reshape(s, h * hd), p["attn.wo"])
    return (out, jnp.mean(kl), jnp.mean(n.astype(jnp.float32)),
            (jnp.max(off), jnp.mean(missed)), keys.reshape(s, -1))


def routed_experts(x, p: dict, z: dict, einsum, held: tuple | None = None,
                   given=None, shift: int = 0):
    """``x [T, d]`` (normed) -> the part of the layer's output that the held
    experts give, the worst misplaced choice with the share of choices the
    reference did not make itself, and the experts chosen ``[T, k]``. ``held =
    (first, count)`` says which of the router's experts ``p``'s stacked expert
    weights are; all of the layer where the stack holds every expert.
    ``given``: each token's chosen experts ``[T, k]``; the reference's own
    choice without, off by ``shift`` ranks."""
    first, count = held or (z["first"], z["held"])
    probs = jax.nn.softmax(einsum("td,de->te", x, p["moe.router"]), axis=-1)
    ranked = jax.lax.stop_gradient(probs)
    chosen = shifted_choice(ranked, z["k"], shift)
    off = missed = jnp.zeros(())
    if given is not None:
        own, chosen = chosen, jnp.any(
            given[:, :, None] == jnp.arange(probs.shape[-1]), axis=1)
        kth = kth_largest(ranked, z["k"])[:, None]
        off = jnp.max(jnp.where(
            jnp.sum(chosen, axis=-1) == z["k"],
            worst_misplaced(ranked, chosen, jnp.ones_like(chosen), kth,
                            kth[:, 0]), jnp.inf))
        missed = jnp.mean(jnp.sum(chosen & ~own, axis=-1) / z["k"])
    weights = jnp.where(chosen, probs, 0.0)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    mine = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=1)

    @jax.checkpoint
    def one(out, args):
        w_e, gate, up, down = args
        hidden = (jax.nn.silu(einsum("td,df->tf", x, gate))
                  * einsum("td,df->tf", x, up))
        return out + w_e[:, None] * einsum("tf,fd->td", hidden, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (mine.T, p["moe.gate"], p["moe.up"], p["moe.down"]))
    # the chosen experts by number, lowest first (sets are what is compared)
    numbers = jnp.sort(jnp.where(chosen, jnp.arange(probs.shape[-1]),
                                 probs.shape[-1]), axis=-1)[:, :z["k"]]
    return out, (off, missed), numbers.astype(jnp.int32)


def block(h, p: dict, z: dict, einsum, given=None, shift=(0, 0)):
    """One layer on a batch ``h [B, S, d]``; returns the new ``h``, the KL
    term and the chosen keys a query (means over the batch), the four numbers
    of :func:`choice_margins`, and the choices made ``(keys [B, S, words],
    experts [B, S, k])``. ``given``: choices to follow, in that form;
    ``shift``: the fault planted in the reference's own ``(keys, experts)``."""
    b, s, d = h.shape
    x = rms_norm(h, p["ln1.g"], z["eps"])
    if given is None:
        out, kl, n, (key_off, key_miss), keys = jax.lax.map(
            lambda row: attention(row, p, z, einsum, shift=shift[0]), x)
    else:
        out, kl, n, (key_off, key_miss), keys = jax.lax.map(
            lambda a: attention(a[0], p, z, einsum, a[1]), (x, given[0]))
    h = h + out
    x2 = rms_norm(h, p["ln2.g"], z["eps"]).reshape(b * s, d)
    out, (expert_off, expert_miss), experts = routed_experts(
        x2, p, z, einsum, shift=shift[1],
        given=None if given is None else given[1].reshape(b * s, -1))
    h = h + out.reshape(b, s, d)
    return h, (jnp.mean(kl), jnp.mean(n),
               {"key_choice_margin": jnp.max(key_off),
                "expert_choice_margin": expert_off,
                "keys_misplaced_share": jnp.mean(key_miss),
                "experts_misplaced_share": expert_miss},
               (keys, experts.reshape(b, s, -1)))


def forward(w: dict, tokens, c: dict, precision: str = "f32", choices=None,
            shift=(0, 0)):
    """Logits ``[B, S, V]``, the indexer's loss (a mean over layers and
    queries), the mean number of keys a query chose, the four numbers of
    :func:`choice_margins` (margins the worst of the layers, shares their
    mean) and the choices made. ``choices``: ``(keys [L, B, S, words], experts
    [L, B, S, k])`` to follow; the reference's own without, off by ``shift``
    ranks of ``(keys, experts)``."""
    einsum = make_einsum(precision)
    z = sizes_of(c)
    stacked = {k[4:]: x for k, x in w.items() if k.startswith("blk.")}

    @jax.checkpoint
    def body(h, layer):
        return block(h, layer[0], z, einsum, layer[1], shift)

    h, (kl, n, held_to, made) = jax.lax.scan(
        body, w["wte"][tokens] * c.get("embedding_multiplier", 1.0),
        (stacked, choices))
    h = rms_norm(h, w["lnf.g"], z["eps"])
    return (einsum("bsd,dv->bsv", h, w["head.w"]), jnp.mean(kl), jnp.mean(n),
            {k: (jnp.max if k.endswith("margin") else jnp.mean)(v)
             for k, v in held_to.items()}, made)


def attach_choices(tokens, key_choice, expert_choice):
    """``tokens [B, S]`` int32 with the program's choices packed behind each
    row's ids: ``key_choice [L, B, S, S]`` bool and ``expert_choice
    [L, B, S, k]``. A row then carries its own choices through any split of
    the batch into blocks of rows."""
    b, s = tokens.shape
    keys = jnp.moveaxis(pack_bits(key_choice), 0, 1).reshape(b, -1)
    experts = jnp.moveaxis(expert_choice, 0, 1).reshape(b, -1)
    return jnp.concatenate(
        [tokens.astype(jnp.int32), keys, experts.astype(jnp.int32)], axis=1)


def split_choices(inputs, c: dict, seq: int):
    """The inverse: ``(tokens [B, S], choices or None)``."""
    if inputs.shape[1] == seq:
        return inputs, None
    z = sizes_of(c)
    b, words = inputs.shape[0], -(-seq // 32)
    n_keys = z["n"] * seq * words
    keys = inputs[:, seq:seq + n_keys].reshape(b, z["n"], seq, words)
    experts = inputs[:, seq + n_keys:].reshape(b, z["n"], seq, z["k"])
    return inputs[:, :seq], (jnp.moveaxis(keys, 1, 0),
                             jnp.moveaxis(experts, 1, 0))


def choice_margins(w: dict, inputs, seq: int, c: dict) -> dict:
    """The choices ``inputs`` carries (rows of ``seq`` tokens) against this
    reference's own float32 scores. ``key_choice_margin``: the worst misplaced
    key, in units of its query's spread of index scores;
    ``expert_choice_margin``: the worst misplaced expert, in units of its
    token's 8th largest router probability; both infinite where a choice
    handed in is none (a key not yet seen, too few or too many).
    ``keys_misplaced_share`` / ``experts_misplaced_share``: the share of a
    query's keys, and of a token's experts, that the reference itself did not
    choose, a mean over queries, tokens and layers. The margin catches a
    choice that is far off, the share many that are a little off."""
    tokens, choices = split_choices(inputs, c, seq)
    return forward(w, tokens, c, "f32", choices)[3]


def own_choices(w: dict, tokens, c: dict, precision: str = "f32",
                shift=(0, 0)):
    """``tokens`` with the reference's own choices behind each row's ids
    (:func:`attach_choices`'s form), made with the matrix products in
    ``precision`` and off by ``shift`` ranks: sound (``"f32"``, no shift) or
    with a fault planted."""
    keys, experts = forward(w, tokens, c, precision, shift=shift)[4]
    b = tokens.shape[0]
    return jnp.concatenate(
        [tokens.astype(jnp.int32), jnp.moveaxis(keys, 0, 1).reshape(b, -1),
         jnp.moveaxis(experts, 0, 1).reshape(b, -1)], axis=1)


def losses(w: dict, inputs, targets, c: dict, precision: str = "f32"):
    """``(L_LM, L_I)``: mean next-token cross-entropy over the held vocabulary,
    and the indexer's KL term. ``inputs`` may carry choices
    (:func:`attach_choices`)."""
    tokens, choices = split_choices(inputs, c, targets.shape[1])
    logits, kl = forward(w, tokens, c, precision, choices)[:2]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked), kl


def make_loss(c: dict, precision: str = "f32"):
    """What the benchmark's reference loop differentiates: its value is
    ``L_LM``, which is what the program reports as ``loss``, and its gradient
    is that of ``L_LM + L_I``, which is what the program descends."""
    def loss_fn(w, inputs, targets):
        lm, kl = losses(w, inputs, targets, c, precision)
        return lm + (kl - jax.lax.stop_gradient(kl))
    return loss_fn
