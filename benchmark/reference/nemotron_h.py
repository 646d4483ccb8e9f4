"""The decoder of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` ``nemotron_h``)
as plain ``jax.numpy``: forward, loss and, through ``jax.grad``, gradients.
Float32, ``Precision.HIGHEST``, no kernels, no cache, nothing of the program
imported. Written from the layers' equations (ISSUE 34; PERF.md section 4),
with the configuration file's keys (``configs/nemotron-3-nano-30b-a3b.json``).

Every layer is ONE mixer behind one RMSNorm, ``h <- h + mixer(RMSNorm(h))``,
its kind the layer's character of ``hybrid_override_pattern``; after the last
an RMSNorm and an untied head. No bias but the convolution's, no positions.

``M``, Mamba-2 (H heads of P, G groups, state N, K taps):

    [z | xBC | dt] = u W_in         (H P | H P + 2 G N | H wide)
    xBC <- silu(sum_j w_j xBC[t - (K - 1) + j] + b)   a channel, causal
    x [H, P], B [G, N], C [G, N] = split(xBC); head h reads group h // (H / G)
    dt = softplus(dt + dt_bias + dt_bias_shift);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T  (P x N a head);  y_t = h_t C_t + D x_t
    y <- RMSNorm over each group's H P / G channels of (y * silu(z)), gain
    out = y W_out

computed here as the RECURRENCE, token by token (``lax.scan`` over ``t``),
never the chunked form the program uses: the two derivations have to meet.
The scan is checkpointed in blocks of ``SCAN_BLOCK`` tokens (the states at the
blocks' boundaries are kept, the inside is made again on the way back), which
changes no result.

``*``, attention: q 32 heads, k and v 2 heads of 128, causal softmax, scale
``head_dim^-1/2``, 16 query heads a key head, no rotary turn.

``E``, experts: ``s = sigmoid(u W_r)`` over all the router's experts; the
``num_experts_per_tok`` of largest ``s + bias`` are chosen (``bias``: the
correction bias, zero at the seeded start; the lower-numbered of equals);
``w_e = routed_scaling_factor * s_e / sum of the chosen s``; ``out = shared(u)
+ sum_e w_e f_e(u)``, ``f(u) = W_2 relu(W_1 u)^2`` for routed and shared
alike. Only the experts held here (``n_routed_experts`` of the router's
``deployment.published_n_routed_experts``, from ``deployment.first_expert``)
add to the residual: the chip's share of an expert-parallel layer; the shared
expert is whole.

**Choices handed in.** Which expert is a token's sixth is not continuous, so
the comparison that decides ``correct`` hands this reference the program's
choices (``attach_choices`` packs them behind each row's ids) and
``choice_margins`` holds them to this reference's own scores, as
``reference/keye_vl2.py`` does (its helpers are used here).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.keye_vl2 import (kth_largest, shifted_choice,
                                          worst_misplaced)
from benchmark.reference.matmul import make_einsum

MASKED = -1e30
# the correction biases at balance (:func:`balance`): rounds of the published
# rule, the step halved every BALANCE_HOLD rounds from BALANCE_STEP down to
# BALANCE_STEP / 2**11, far under the distance of neighbouring scores
BALANCE_STEP, BALANCE_HOLD, BALANCE_ROUNDS = 0.05, 8, 96
QUERY_BLOCK = 128   # queries scored at a time; no result depends on it
SCAN_BLOCK = 128    # tokens between kept states of the recurrence; nor on it


def sizes_of(c: dict) -> dict:
    dep = c["deployment"]
    pattern = c["hybrid_override_pattern"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "kv": c["num_key_value_heads"], "hd": c["head_dim"],
            "mh": c["mamba_num_heads"], "mp": c["mamba_head_dim"],
            "g": c["n_groups"], "n": c["ssm_state_size"],
            "taps": c["conv_kernel"], "chunk": c["chunk_size"],
            "f": c["moe_intermediate_size"],
            "fs": c["moe_shared_expert_intermediate_size"],
            "held": c["n_routed_experts"],
            "width": dep["published_n_routed_experts"],
            "first": dep["first_expert"], "k": c["num_experts_per_tok"],
            "scale": c["routed_scaling_factor"],
            "renorm": c["norm_topk_prob"], "v": c["vocab_size"],
            "pattern": pattern, "eps": c["norm_eps"],
            "dt_shift": c.get("dt_bias_shift", 0.0)}


def weight_spec(c: dict) -> dict:
    """Leaf -> ``(shape, kind)``; layer ``i``'s leaves are named ``l<i>.*``.
    One leaf a layer and matrix, no stack over layers: the layers differ in
    kind, and a gradient with respect to a slice of a stack is padded to the
    whole stack's size, once a layer, which this model's size cannot afford.
    Kinds are ``harness/weights.py``'s; the convolution's taps are seeded as
    gains (configs/nemotron-3-nano-30b-a3b.json, ``assumed``, says why)."""
    z = sizes_of(c)
    d, inner = z["d"], z["mh"] * z["mp"]
    conv = inner + 2 * z["g"] * z["n"]
    spec = {"wte": ((z["v"], d), "w"), "lnf.g": ((d,), "gain"),
            "head.w": ((d, z["v"]), "w")}
    per_kind = {
        "M": (("ln.g", (d,), "gain"),
              ("win", (d, inner + conv + z["mh"]), "w"),
              ("conv.w", (z["taps"], conv), "gain"),
              ("conv.b", (conv,), "bias"), ("alog", (z["mh"],), "w"),
              ("D", (z["mh"],), "gain"), ("dtb", (z["mh"],), "bias"),
              ("gn.g", (inner,), "gain"), ("wout", (inner, d), "w")),
        "*": (("ln.g", (d,), "gain"), ("wq", (d, z["h"] * z["hd"]), "w"),
              ("wk", (d, z["kv"] * z["hd"]), "w"),
              ("wv", (d, z["kv"] * z["hd"]), "w"),
              ("wo", (z["h"] * z["hd"], d), "w")),
        "E": (("ln.g", (d,), "gain"), ("router", (d, z["width"]), "w"),
              ("w1", (z["held"], d, z["f"]), "w"),
              ("w2", (z["held"], z["f"], d), "w"),
              ("s1", (d, z["fs"]), "w"), ("s2", (z["fs"], d), "w"))}
    for i, kind in enumerate(z["pattern"]):
        for name, shape, how in per_kind[kind]:
            spec[f"l{i}.{name}"] = (shape, how)
    return spec


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def ssm_recurrence(x, dt, a, b, c):
    """One row, token by token: ``x [S, H, P]``, ``dt [S, H]``, ``a [H]``,
    ``b``, ``c [S, G, N]`` -> ``y [S, H, P]`` (without the ``D x`` skip)."""
    s, h, p = x.shape
    r = h // b.shape[1]

    def token(state, t):
        x_t, dt_t, b_t, c_t = t
        b_h, c_h = jnp.repeat(b_t, r, axis=0), jnp.repeat(c_t, r, axis=0)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    @jax.checkpoint
    def block(state, ts):
        return jax.lax.scan(token, state, ts)

    size = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    cut = lambda t: t.reshape(s // size, size, *t.shape[1:])   # noqa: E731
    _, y = jax.lax.scan(block, jnp.zeros((h, p, b.shape[2]), jnp.float32),
                        (cut(x), cut(dt), cut(b), cut(c)))
    return y.reshape(s, h, p)


def mamba(u, p: dict, z: dict, einsum):
    """One row ``u [S, d]`` (normed) -> what the layer adds to the residual,
    and the mean over heads and chunks of ``chunk`` tokens of the share of a
    state that crosses the chunk (what the program counts as
    ``ssm_chunk_carry``)."""
    s = u.shape[0]
    h, hp, g, n, taps = z["mh"], z["mp"], z["g"], z["n"], z["taps"]
    inner = h * hp
    proj = einsum("sd,de->se", u, p["win"])
    gate, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * g * n], axis=-1)
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[j:j + s] * p["conv.w"][j]
                          for j in range(taps)) + p["conv.b"])
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    x = x.reshape(s, h, hp)
    dt = jax.nn.softplus(dt + p["dtb"] + z["dt_shift"])
    a = -jnp.exp(p["alog"])
    y = ssm_recurrence(x, dt, a, b.reshape(s, g, n), c.reshape(s, g, n))
    y = (y + p["D"][:, None] * x).reshape(s, inner) * jax.nn.silu(gate)
    y = rms_norm(y.reshape(s, g, inner // g), 1.0, z["eps"]).reshape(
        s, inner) * p["gn.g"]
    chunk = z["chunk"]
    crossing = jnp.exp(jnp.sum(jnp.pad(dt * a, ((0, -s % chunk), (0, 0)))
                               .reshape(-1, chunk, h), axis=1))
    return einsum("se,ed->sd", y, p["wout"]), jnp.mean(crossing)


def attention(u, p: dict, z: dict, einsum):
    """One row ``u [S, d]`` (normed): causal softmax attention, ``h / kv``
    query heads a key head, by blocks of queries."""
    s = u.shape[0]
    h, kv, hd = z["h"], z["kv"], z["hd"]
    q = einsum("sd,de->se", u, p["wq"]).reshape(s, kv, h // kv, hd)
    k = einsum("sd,de->se", u, p["wk"]).reshape(s, kv, hd)
    v = einsum("sd,de->se", u, p["wv"]).reshape(s, kv, hd)
    tile = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    pos = jnp.arange(s)

    @jax.checkpoint
    def query_block(args):
        q_b, t_b = args
        scores = einsum("qhgd,khd->hgqk", q_b, k) / math.sqrt(hd)
        causal = pos[None, :] <= t_b[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, MASKED), axis=-1)
        return einsum("hgqk,khd->qhgd", probs, v).reshape(tile, h * hd)

    cut = lambda t: t.reshape(s // tile, tile, *t.shape[1:])   # noqa: E731
    out = jax.lax.map(query_block, (cut(q), cut(pos))).reshape(s, h * hd)
    return einsum("se,ed->sd", out, p["wo"])


def balance(scores, k: int):
    """The correction biases ``[width]`` at which every expert of a layer is
    chosen equally often on these tokens: ``scores [T, width]`` (the router's
    sigmoids) -> the fixed point of the published rule (arXiv:2412.19437,
    section 2.1.2: raise the bias of an expert under the mean load, lower it
    over), the step halved as it goes, mean zero. What a checkpoint that is
    being continued has learnt over its steps and a seeded state lacks: at no
    bias the normed state's common component times a random router loads the
    experts 2-4 times the mean by layer (PERF.md section 7, PR 34 (iii))."""
    mean = scores.shape[0] * k / scores.shape[1]

    def round_(i, bias):
        load = jnp.sum(shifted_choice(scores + bias, k, 0), axis=0)
        step = BALANCE_STEP * 0.5 ** (i // BALANCE_HOLD)
        return bias + step * jnp.sign(mean - load)

    bias = jax.lax.fori_loop(0, BALANCE_ROUNDS, round_,
                             jnp.zeros(scores.shape[1], jnp.float32))
    return bias - jnp.mean(bias)


def experts(x, p: dict, z: dict, einsum, held: tuple | None = None,
            given=None, shift: int = 0, bias=None):
    """``x [T, d]`` (normed) -> what the shared expert and the held routed
    experts add, the worst misplaced choice with the share of choices the
    reference did not make itself, the experts chosen ``[T, k]`` and every
    expert's load ``[width]``. ``held = (first, count)`` says which of the
    router's experts ``p``'s stacks are; ``given``: each token's chosen
    experts ``[T, k]``, the reference's own choice without, off by ``shift``
    ranks; ``bias``: the correction bias the choice adds to the scores."""
    first, count = held or (z["first"], z["held"])
    scores = jax.nn.sigmoid(einsum("td,de->te", x, p["router"]))
    ranked = jax.lax.stop_gradient(scores if bias is None else scores + bias)
    chosen = shifted_choice(ranked, z["k"], shift)
    off = missed = jnp.zeros(())
    if given is not None:
        own, chosen = chosen, jnp.any(
            given[:, :, None] == jnp.arange(scores.shape[-1]), axis=1)
        kth = kth_largest(ranked, z["k"])[:, None]
        off = jnp.max(jnp.where(
            jnp.sum(chosen, axis=-1) == z["k"],
            worst_misplaced(ranked, chosen, jnp.ones_like(chosen), kth,
                            kth[:, 0]), jnp.inf))
        missed = jnp.mean(jnp.sum(chosen & ~own, axis=-1) / z["k"])
    weights = jnp.where(chosen, scores, 0.0)
    if z["renorm"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    mine = jax.lax.dynamic_slice_in_dim(weights * z["scale"], first, count,
                                        axis=1)

    def expert(w1, w2):
        return einsum("tf,fd->td", jnp.square(jax.nn.relu(
            einsum("td,df->tf", x, w1))), w2)

    @jax.checkpoint
    def one(out, args):
        w_e, w1, w2 = args
        return out + w_e[:, None] * expert(w1, w2), None

    out, _ = jax.lax.scan(one, jax.checkpoint(expert)(p["s1"], p["s2"]),
                          (mine.T, p["w1"], p["w2"]))
    numbers = jnp.sort(jnp.where(chosen, jnp.arange(scores.shape[-1]),
                                 scores.shape[-1]), axis=-1)[:, :z["k"]]
    return (out, (off, missed), numbers.astype(jnp.int32),
            jnp.sum(chosen, axis=0).astype(jnp.float32))


def forward(w: dict, tokens, c: dict, precision: str = "f32", choices=None,
            shift: int = 0, bias=None, found: list | None = None):
    """Logits ``[B, S, V]``; ``{"expert_choice_margin",
    "experts_misplaced_share"}`` (the worst and the mean over the ``E``
    layers); the choices made ``[E layers, B, S, k]``; the mean over ``M``
    layers of the share of a state that crosses a chunk (the program's
    ``ssm_chunk_carry``); and every ``E`` layer's loads
    ``[E layers, width]``. ``choices`` to follow, else the reference's own off
    by ``shift`` ranks; ``bias [E layers, width]`` as :func:`experts`.
    ``found``: a list that receives every ``E`` layer's bias at balance on
    these tokens (:func:`balance`), each layer choosing under its own as the
    pass goes on; ``bias`` is not read then."""
    einsum = make_einsum(precision)
    z = sizes_of(c)
    b, s = tokens.shape
    h = w["wte"][tokens] * c.get("embedding_multiplier", 1.0)
    routed = 0      # E layers so far: where choices and bias are indexed
    margins, shares, made, decays, loads = [], [], [], [], []
    for i, kind in enumerate(z["pattern"]):
        p = {k.partition(".")[2]: x for k, x in w.items()
             if k.startswith(f"l{i}.")}
        if kind == "M":
            @jax.checkpoint
            def layer(h, p):
                return jax.lax.map(lambda row: mamba(
                    rms_norm(row, p["ln.g"], z["eps"]), p, z, einsum), h)
            out, decay = layer(h, p)
            decays.append(jnp.mean(decay))
        elif kind == "*":
            @jax.checkpoint
            def layer(h, p):
                return jax.lax.map(lambda row: attention(
                    rms_norm(row, p["ln.g"], z["eps"]), p, z, einsum), h)
            out = layer(h, p)
        else:
            given = (None if choices is None
                     else choices[routed].reshape(b * s, -1))
            own_bias = None if bias is None else bias[routed]
            routed += 1
            if found is not None:
                own_bias = balance(jax.nn.sigmoid(einsum(
                    "td,de->te", rms_norm(h, p["ln.g"], z["eps"]).reshape(
                        b * s, -1), p["router"])), z["k"])
                found.append(own_bias)

            @jax.checkpoint
            def layer(h, p, given, own_bias):
                return experts(
                    rms_norm(h, p["ln.g"], z["eps"]).reshape(b * s, -1), p, z,
                    einsum, given=given, shift=shift, bias=own_bias)
            out, (off, missed), numbers, load = layer(h, p, given, own_bias)
            out = out.reshape(h.shape)
            margins.append(off), shares.append(missed), loads.append(load)
            made.append(numbers.reshape(b, s, -1))
        h = h + out
    h = rms_norm(h, w["lnf.g"], z["eps"])
    stack = lambda xs: jnp.stack(xs) if xs else jnp.zeros((0,))  # noqa: E731
    held_to = {"expert_choice_margin": jnp.max(stack(margins), initial=0.0),
               "experts_misplaced_share": jnp.sum(stack(shares))
               / max(len(shares), 1)}
    return (einsum("bsd,dv->bsv", h, w["head.w"]), held_to, stack(made),
            jnp.sum(stack(decays)) / max(len(decays), 1), stack(loads))


def attach_choices(tokens, expert_choice):
    """``tokens [B, S]`` int32 with the program's choices ``expert_choice [E
    layers, B, S, k]`` packed behind each row's ids: a row then carries its
    own choices through any split of the batch into blocks of rows."""
    experts_ = jnp.moveaxis(expert_choice, 0, 1).reshape(tokens.shape[0], -1)
    return jnp.concatenate([tokens.astype(jnp.int32),
                            experts_.astype(jnp.int32)], axis=1)


def split_choices(inputs, c: dict, seq: int):
    """The inverse: ``(tokens [B, S], choices or None)``."""
    if inputs.shape[1] == seq:
        return inputs, None
    layers = c["hybrid_override_pattern"].count("E")
    packed = inputs[:, seq:].reshape(inputs.shape[0], layers, seq,
                                     c["num_experts_per_tok"])
    return inputs[:, :seq], jnp.moveaxis(packed, 1, 0)


def balanced_bias(w: dict, tokens, c: dict):
    """``[E layers, width]``: every routed layer's correction biases at
    balance on ``tokens [B, S]``, layer by layer in one float32 pass."""
    found: list = []
    forward(w, tokens, c, "f32", found=found)
    return jnp.stack(found)


def choice_margins(w: dict, inputs, seq: int, c: dict, bias=None) -> dict:
    """The choices ``inputs`` carries against this reference's own float32
    scores under the seeded start's ``bias`` (none: zeros).
    ``expert_choice_margin``: the worst
    misplaced expert, in units of its token's k-th largest score, infinite
    where a choice handed in is none; ``experts_misplaced_share``: the share
    of a token's experts that the reference itself did not choose, a mean
    over tokens and layers."""
    tokens, choices = split_choices(inputs, c, seq)
    return forward(w, tokens, c, "f32", choices, bias=bias)[1]


def own_choices(w: dict, tokens, c: dict, precision: str = "f32",
                shift: int = 0):
    """``tokens`` with the reference's own choices behind each row's ids,
    made with the matrix products in ``precision`` and off by ``shift``
    ranks: sound (``"f32"``, no shift) or with a fault planted."""
    return attach_choices(tokens, forward(w, tokens, c, precision,
                                          shift=shift)[2])


def loss(w: dict, inputs, targets, c: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over the held vocabulary. ``inputs`` may
    carry choices (:func:`attach_choices`)."""
    tokens, choices = split_choices(inputs, c, targets.shape[1])
    logits = forward(w, tokens, c, precision, choices)[0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def make_loss(c: dict, precision: str = "f32"):
    """What the benchmark's reference loop differentiates."""
    def loss_fn(w, inputs, targets):
        return loss(w, inputs, targets, c, precision)
    return loss_fn
