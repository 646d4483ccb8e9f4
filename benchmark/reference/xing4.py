"""The decoder of Xing4.0-29B-A4B (``model_type`` ``xing4_0``) as plain
``jax.numpy``: forward, loss and, through ``jax.grad``, gradients. Float32,
``Precision.HIGHEST``, no kernels, no cache, nothing of the program imported.
Written from the layers' equations (ISSUE 42; docs/layer_spec.md), with the
configuration file's keys (``configs/xing4.0-29b-a4b.json``).

The residual path is ``n = hc_mult`` streams a token, ``x [n, C]``
(manifold-constrained hyper-connections, arXiv:2512.24880). Around every
sublayer ``F`` (attention, then the MLP), with float32 coefficients:

    xt = RMSNorm(vec(x)) over all n C numbers, gain g
    [Hpre~ | Hpost~ | Hres~] = a * (xt phi) + b     (a: one scalar a part)
    Hpre = sigmoid(Hpre~) [n];  Hpost = 2 sigmoid(Hpost~) [n]
    Hres = SK(clip(Hres~ + hc_res_diag_start I, clamp)) [n, n]:  M = exp(.),
           then hc_sinkhorn_iters rounds of (each row over its sum + hc_eps,
           then each column over its sum + hc_eps), a Python loop
    u = Hpre x;  y = F(RMSNorm_l(u));  x' = Hres x + Hpost^T y

Entry: the embedding copied to the n streams. Exit: the streams summed, then
the final RMSNorm and the untied head.

``F`` of the first sublayer, MLA (DeepSeek-V2/V3), in its expanded form with
the ``[S, S]`` scores:

    cq = RMSNorm(u W_dq);  [q_nope | q_rope] = cq W_uq a head
    [ckv | k_rope] = u W_dkv;  ckv <- RMSNorm(ckv)
    k_nope = ckv W_uk, v = ckv W_uv a head   (W_ukv's two halves a head, kept
                                              as two leaves)
    q_rope, k_rope turned by YaRN's frequencies on interleaved pairs; k_rope
    is ONE head shared by all query heads
    scores (q_nope . k_nope + q_rope . k_rope) (nope + rope)^-1/2
           (0.1 ln factor + 1)^2, causal softmax, times v;  out concat W_o

``F`` of the second: layers under ``first_k_dense_replace``
``down(silu(gate u) * up u)``; the others and the MTP block ``s = sigmoid(u
W_r)`` over all the router's experts, the ``num_experts_per_tok`` of largest
``s + bias`` (the lower-numbered of equals), weights the chosen ``s``
renormalised times ``routed_scaling_factor``, over the experts held here
(``n_routed_experts`` of ``deployment.published_n_routed_experts`` from
``deployment.first_expert``), plus the SwiGLU shared expert for every token.

MTP, depth 1: ``h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_eh`` with
``h_i`` the trunk's summed streams before the final norm; one expert layer as
above on its own streams (copied in, summed out); the shared final norm and
head. ``L = CE(main, t_{i+1}) + mtp_loss_weight CE(mtp, t_{i+2})``, the second
over the positions that have a token after next (the row's last reads the
row's first token in ``t_{i+1}``'s place, which no other position sees, and is
left out of the loss).

**Choices handed in.** As ``reference/nemotron_h.py``: the comparison hands
this reference the program's choices of experts (``attach_choices``) and
``choice_margins`` holds them to this reference's own scores.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.keye_vl2 import (kth_largest, shifted_choice,
                                          worst_misplaced)
from benchmark.reference.matmul import make_einsum
from benchmark.reference.nemotron_h import attach_choices, balance  # noqa: F401

MASKED = -1e30
QUERY_BLOCK = 128   # queries scored at a time; no result depends on it


def sizes_of(c: dict) -> dict:
    dep, yarn = c["deployment"], c["rope_scaling"]
    dense = c["first_k_dense_replace"]
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "qr": c["q_lora_rank"], "kvr": c["kv_lora_rank"],
            "nope": c["qk_nope_head_dim"], "rope": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "fd": c["intermediate_size"],
            "f": c["moe_intermediate_size"],
            "fs": c["moe_intermediate_size"] * c["n_shared_experts"],
            "held": c["n_routed_experts"],
            "width": dep["published_n_routed_experts"],
            "first": dep["first_expert"], "k": c["num_experts_per_tok"],
            "scale": c["routed_scaling_factor"],
            "renorm": c["norm_topk_prob"], "v": c["vocab_size"],
            "eps": c["rms_norm_eps"], "theta": c["rope_theta"],
            "factor": yarn["factor"], "fast": yarn["beta_fast"],
            "slow": yarn["beta_slow"],
            "orig": yarn["original_max_position_embeddings"],
            "n": c["hc_mult"], "iters": c["hc_sinkhorn_iters"],
            "hc_eps": c["hc_eps"],
            "clamp": (c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]),
            "diag": c.get("hc_res_diag_start", 0.0),
            "layers": c["num_hidden_layers"], "dense": dense,
            "mtp": c["num_nextn_predict_layers"],
            "lam": c.get("mtp_loss_weight", 0.0),
            "embed": c.get("embedding_multiplier", 1.0)}


def blocks_of(z: dict) -> list:
    """``(prefix, routed)`` of every block in the order it runs: the trunk's
    layers, then the MTP module's one."""
    return ([(f"l{i}", i >= z["dense"]) for i in range(z["layers"])]
            + [("m", True)] * z["mtp"])


def routed_blocks(c: dict) -> int:
    return sum(routed for _, routed in blocks_of(sizes_of(c)))


def weight_spec(c: dict) -> dict:
    """Leaf -> ``(shape, kind)``, one leaf a layer and matrix (no stacks:
    ``reference/nemotron_h.py`` says why). Kinds are ``harness/weights.py``'s.
    The hyper-connections' three scalars ``a`` are seeded like matrices, N(0,
    0.02): the paper starts them at 0.01."""
    z = sizes_of(c)
    d, h, n = z["d"], z["h"], z["n"]
    spec = {"wte": ((z["v"], d), "w"), "lnf.g": ((d,), "gain"),
            "head.w": ((d, z["v"]), "w")}
    hyper = lambda at: (                                        # noqa: E731
        (f"{at}.g", (n * d,), "gain"),
        (f"{at}.phi", (n * d, n * (n + 2)), "w"),
        (f"{at}.a", (3,), "w"), (f"{at}.b", (n * (n + 2),), "bias"))
    shared = (("ln1.g", (d,), "gain"), ("ln2.g", (d,), "gain"),
              ("wdq", (d, z["qr"]), "w"), ("qn.g", (z["qr"],), "gain"),
              ("wuq", (z["qr"], h * (z["nope"] + z["rope"])), "w"),
              ("wdkv", (d, z["kvr"] + z["rope"]), "w"),
              ("kvn.g", (z["kvr"],), "gain"),
              ("wuk", (z["kvr"], h * z["nope"]), "w"),
              ("wuv", (z["kvr"], h * z["dv"]), "w"),
              ("wo", (h * z["dv"], d), "w")) + hyper("hca") + hyper("hcm")
    dense = (("wg", (d, z["fd"]), "w"), ("wu", (d, z["fd"]), "w"),
             ("wd", (z["fd"], d), "w"))
    routed = (("router", (d, z["width"]), "w"),
              ("w1g", (z["held"], d, z["f"]), "w"),
              ("w1u", (z["held"], d, z["f"]), "w"),
              ("w2", (z["held"], z["f"], d), "w"),
              ("sg", (d, z["fs"]), "w"), ("su", (d, z["fs"]), "w"),
              ("sd", (z["fs"], d), "w"))
    for prefix, is_routed in blocks_of(z):
        for name, shape, how in shared + (routed if is_routed else dense):
            spec[f"{prefix}.{name}"] = (shape, how)
    if z["mtp"]:
        spec.update({"m.hn.g": ((d,), "gain"), "m.en.g": ((d,), "gain"),
                     "m.weh": ((2 * d, d), "w")})
    return spec


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def yarn_inv_freq(z: dict):
    """Per pair of the rotary part: ``theta^(-2i/rope)`` where the pair makes
    ``beta_fast`` turns or more in the original context, that over ``factor``
    where it makes ``beta_slow`` or fewer, the linear blend between (HF
    ``DeepseekV3YarnRotaryEmbedding``)."""
    dim, base = z["rope"], z["theta"]

    def pair_turning(turns):
        return dim * math.log(z["orig"] / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(pair_turning(z["fast"])), 0)
    high = min(math.ceil(pair_turning(z["slow"])), dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    stretched = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                         / (high - low), 0.0, 1.0)
    return plain / z["factor"] * stretched + plain * (1.0 - stretched)


def rope(x, z: dict):
    """``x [S, heads, rope]`` turned at positions ``0..S-1``, interleaved
    pairs; cos/sin scale ``mscale / mscale_all_dim`` = 1."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * yarn_inv_freq(z)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def softmax_scale(z: dict) -> float:
    scale = (z["nope"] + z["rope"]) ** -0.5
    if z["factor"] > 1:
        scale *= (0.1 * math.log(z["factor"]) + 1.0) ** 2
    return scale


def attention(u, p: dict, z: dict, einsum):
    """One row ``u [S, d]`` (normed): MLA expanded, by blocks of queries."""
    s = u.shape[0]
    h, nope, rp, dv = z["h"], z["nope"], z["rope"], z["dv"]
    cq = rms_norm(einsum("sd,dr->sr", u, p["wdq"]), p["qn.g"], z["eps"])
    q = einsum("sr,re->se", cq, p["wuq"]).reshape(s, h, nope + rp)
    down = einsum("sd,dr->sr", u, p["wdkv"])
    ckv = rms_norm(down[:, :z["kvr"]], p["kvn.g"], z["eps"])
    k_rope = rope(down[:, None, z["kvr"]:], z)[:, 0]            # [S, rope]
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], z)
    k_nope = einsum("sr,re->se", ckv, p["wuk"]).reshape(s, h, nope)
    v = einsum("sr,re->se", ckv, p["wuv"]).reshape(s, h, dv)
    scale = softmax_scale(z)
    tile = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    pos = jnp.arange(s)

    @jax.checkpoint
    def query_block(args):
        qn_b, qr_b, t_b = args
        scores = (einsum("qhd,khd->hqk", qn_b, k_nope)
                  + einsum("qhd,kd->hqk", qr_b, k_rope)) * scale
        causal = pos[None, :] <= t_b[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, MASKED), axis=-1)
        return einsum("hqk,khd->qhd", probs, v).reshape(tile, h * dv)

    cut = lambda t: t.reshape(s // tile, tile, *t.shape[1:])   # noqa: E731
    out = jax.lax.map(query_block, (cut(q_nope), cut(q_rope), cut(pos)))
    return einsum("se,ed->sd", out.reshape(s, h * dv), p["wo"])


def hyper(x, p: dict, at: str, z: dict, einsum):
    """The coefficients around one sublayer from the streams ``x [B, S, n,
    C]``: ``(Hpre [B,S,n], Hpost [B,S,n], Hres [B,S,n,n])``."""
    b, s, n, c = x.shape
    xt = rms_norm(x.reshape(b, s, n * c), p[f"{at}.g"], z["eps"])
    raw = einsum("bsk,kj->bsj", xt, p[f"{at}.phi"])
    a, bias = p[f"{at}.a"], p[f"{at}.b"]
    pre = a[0] * raw[..., :n] + bias[:n]
    post = a[1] * raw[..., n:2 * n] + bias[n:2 * n]
    res = (a[2] * raw[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
    res = res + z["diag"] * jnp.eye(n, dtype=res.dtype)
    m = jnp.exp(jnp.clip(res, *z["clamp"]))
    for _ in range(z["iters"]):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + z["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + z["hc_eps"])
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), m


def experts(x, p: dict, z: dict, einsum, held: tuple | None = None,
            given=None, shift: int = 0, bias=None):
    """``x [T, d]`` (normed) -> what the shared expert and the held routed
    experts add, the worst misplaced choice with the share of choices the
    reference did not make itself, the experts chosen ``[T, k]`` and every
    expert's load ``[width]``; arguments as
    ``reference/nemotron_h.py::experts``."""
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

    def expert(wg, wu, wd):
        return einsum("tf,fd->td", jax.nn.silu(einsum("td,df->tf", x, wg))
                      * einsum("td,df->tf", x, wu), wd)

    @jax.checkpoint
    def one(out, args):
        w_e, wg, wu, wd = args
        return out + w_e[:, None] * expert(wg, wu, wd), None

    out, _ = jax.lax.scan(
        one, jax.checkpoint(expert)(p["sg"], p["su"], p["sd"]),
        (mine.T, p["w1g"], p["w1u"], p["w2"]))
    numbers = jnp.sort(jnp.where(chosen, jnp.arange(scores.shape[-1]),
                                 scores.shape[-1]), axis=-1)[:, :z["k"]]
    return (out, (off, missed), numbers.astype(jnp.int32),
            jnp.sum(chosen, axis=0).astype(jnp.float32))


def forward(w: dict, tokens, c: dict, precision: str = "f32", choices=None,
            shift: int = 0, bias=None, found: list | None = None):
    """``(logits [B, S, V], the MTP module's logits [B, S, V] or None, the
    choice margins, the choices made [routed blocks, B, S, k], the mean over
    sublayers of the mass of Hres off its diagonal over n, every routed
    block's loads)``. ``choices`` / ``shift`` / ``bias [routed blocks,
    width]`` / ``found`` as ``reference/nemotron_h.py::forward``; the routed
    blocks count the trunk's, then the MTP module's."""
    einsum = make_einsum(precision)
    z = sizes_of(c)
    b, s = tokens.shape
    n = z["n"]
    margins, shares, made, loads, offdiag = [], [], [], [], []
    routed_so_far = [0]

    def block(x, prefix, is_routed):
        p = {k.partition(".")[2]: v for k, v in w.items()
             if k.startswith(prefix + ".")}

        def sublayer(x, at, ln, f):
            pre, post, res = hyper(x, p, at, z, einsum)
            u = jnp.einsum("bsn,bsnc->bsc", pre, x)
            y, extra = f(rms_norm(u, p[ln], z["eps"]))
            share = jnp.mean(1.0 - jnp.trace(res, axis1=-2, axis2=-1) / n)
            return (jnp.einsum("bsnm,bsmc->bsnc", res, x)
                    + post[..., None] * y[:, :, None, :]), (share, extra)

        @jax.checkpoint
        def attend(x, p):
            return sublayer(x, "hca", "ln1.g", lambda u: (jax.lax.map(
                lambda row: attention(row, p, z, einsum), u), ()))

        x, (share, _) = attend(x, p)
        offdiag.append(share)
        if not is_routed:
            @jax.checkpoint
            def feed(x, p):
                return sublayer(x, "hcm", "ln2.g", lambda u: (einsum(
                    "bsf,fd->bsd", jax.nn.silu(einsum(
                        "bsd,df->bsf", u, p["wg"])) * einsum(
                            "bsd,df->bsf", u, p["wu"]), p["wd"]), ()))
            x, (share, _) = feed(x, p)
            offdiag.append(share)
            return x
        j = routed_so_far[0]
        routed_so_far[0] += 1
        given = None if choices is None else choices[j].reshape(b * s, -1)
        own_bias = None if bias is None else bias[j]
        if found is not None:
            pre, _, _ = hyper(x, p, "hcm", z, einsum)
            u = rms_norm(jnp.einsum("bsn,bsnc->bsc", pre, x), p["ln2.g"],
                         z["eps"]).reshape(b * s, -1)
            own_bias = balance(jax.nn.sigmoid(einsum(
                "td,de->te", u, p["router"])), z["k"])
            found.append(own_bias)

        @jax.checkpoint
        def feed(x, p, given, own_bias):
            def f(u):
                out, *rest = experts(u.reshape(b * s, -1), p, z, einsum,
                                     given=given, shift=shift, bias=own_bias)
                return out.reshape(u.shape), rest
            return sublayer(x, "hcm", "ln2.g", f)

        x, (share, ((off, missed), numbers, load)) = feed(x, p, given,
                                                          own_bias)
        offdiag.append(share)
        margins.append(off), shares.append(missed), loads.append(load)
        made.append(numbers.reshape(b, s, -1))
        return x

    def streams(h):
        return jnp.broadcast_to(h[:, :, None, :], (b, s, n, h.shape[-1]))

    embed = lambda ids: w["wte"][ids] * z["embed"]              # noqa: E731
    x = streams(embed(tokens))
    for prefix, is_routed in blocks_of(z)[:z["layers"]]:
        x = block(x, prefix, is_routed)
    h = jnp.sum(x, axis=2)
    head = lambda h: einsum("bsd,dv->bsv", rms_norm(            # noqa: E731
        h, w["lnf.g"], z["eps"]), w["head.w"])
    logits, ahead = head(h), None
    if z["mtp"]:
        joined = jnp.concatenate(
            [rms_norm(h, w["m.hn.g"], z["eps"]),
             rms_norm(embed(jnp.roll(tokens, -1, axis=1)), w["m.en.g"],
                      z["eps"])], axis=-1)
        x2 = block(streams(einsum("bse,ed->bsd", joined, w["m.weh"])), "m",
                   True)
        ahead = head(jnp.sum(x2, axis=2))
    stack = lambda xs: jnp.stack(xs) if xs else jnp.zeros((0,))  # noqa: E731
    held_to = {"expert_choice_margin": jnp.max(stack(margins), initial=0.0),
               "experts_misplaced_share": jnp.sum(stack(shares))
               / max(len(shares), 1)}
    return (logits, ahead, held_to, stack(made), jnp.mean(stack(offdiag)),
            stack(loads))


def split_choices(inputs, c: dict, seq: int):
    """``(tokens [B, S], choices [routed blocks, B, S, k] or None)``: the
    inverse of ``attach_choices``."""
    if inputs.shape[1] == seq:
        return inputs, None
    packed = inputs[:, seq:].reshape(inputs.shape[0], routed_blocks(c), seq,
                                     c["num_experts_per_tok"])
    return inputs[:, :seq], jnp.moveaxis(packed, 1, 0)


def balanced_bias(w: dict, tokens, c: dict):
    """``[routed blocks, width]``: every routed block's correction biases at
    balance on ``tokens [B, S]``, block by block in one float32 pass."""
    found: list = []
    forward(w, tokens, c, "f32", found=found)
    return jnp.stack(found)


def choice_margins(w: dict, inputs, seq: int, c: dict, bias=None) -> dict:
    """``reference/nemotron_h.py::choice_margins`` for this model."""
    tokens, choices = split_choices(inputs, c, seq)
    return forward(w, tokens, c, "f32", choices, bias=bias)[2]


def cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def mtp_cross_entropy(ahead, targets):
    """The module's logits at position ``i`` against token ``i + 2``, which
    is ``targets[i + 1]``; the row's last position has none."""
    return cross_entropy(ahead[:, :-1], targets[:, 1:])


def losses(w: dict, inputs, targets, c: dict, precision: str = "f32"):
    """``(CE of the main head, CE of the MTP module or 0)``. ``inputs`` may
    carry choices (``attach_choices``)."""
    tokens, choices = split_choices(inputs, c, targets.shape[1])
    logits, ahead = forward(w, tokens, c, precision, choices)[:2]
    second = (mtp_cross_entropy(ahead, targets) if ahead is not None
              else jnp.zeros(()))
    return cross_entropy(logits, targets), second


def make_loss(c: dict, precision: str = "f32"):
    """What the benchmark's reference loop differentiates: its value is the
    main head's cross-entropy, which is what the program reports as ``loss``,
    and its gradient that of ``L = CE(main) + mtp_loss_weight CE(mtp)``, which
    is what the program descends."""
    lam = sizes_of(c)["lam"]

    def loss_fn(w, inputs, targets):
        main, second = losses(w, inputs, targets, c, precision)
        return main + lam * (second - jax.lax.stop_gradient(second))
    return loss_fn
