"""The decoder of JoyAI-LLM-Flash (``model_type`` ``joyai_llm_flash``) as plain
``jax.numpy``: forward, both losses and, through ``jax.grad``, gradients.
Float32, ``Precision.HIGHEST``, no kernels, no cache, nothing of the program
imported. Written from the layers' equations (ISSUE 44; docs/layer_spec.md),
with the configuration file's keys (``configs/joyai-llm-flash.json``).

One residual stream, pre-norm blocks:

    x <- x + MLA(RMSNorm(x));  x <- x + F(RMSNorm(x))

MLA (DeepSeek-V2/V3) in its expanded form with the ``[S, S]`` scores, by
blocks of queries:

    cq = RMSNorm(u W_dq);  [q_nope | q_rope] = cq W_uq a head
    [ckv | k_rope] = u W_dkv;  ckv <- RMSNorm(ckv)
    k_nope = ckv W_uk, v = ckv W_uv a head
    q_rope, k_rope turned by RoPE on interleaved pairs, pair i of position t
    by the angle t theta^(-2i/rope), positions as they are (``rope_scaling``
    is null: no other frequencies, no factor on the scores); k_rope is ONE
    head shared by all query heads
    scores (q_nope . k_nope + q_rope . k_rope) (nope + rope)^-1/2, causal
    softmax, times v;  out concat W_o

``F``: layers under ``first_k_dense_replace`` ``down(silu(gate u) * up u)``;
the others and the MTP block ``reference/xing4.py::experts`` (imported: the
same routed layer: sigmoid scores over all the router's experts, the
``num_experts_per_tok`` of largest ``s + bias``, the lower-numbered of equals,
weights the chosen ``s`` renormalised times ``routed_scaling_factor``, over
the experts held here, plus the SwiGLU shared expert for every token).

MTP, depth 1: ``h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] W_eh`` with
``h_i`` the trunk's output before the final norm; one routed block as above;
the shared final norm and head. ``L = CE(main, t_{i+1}) + mtp_loss_weight
CE(mtp, t_{i+2})``, the second over the positions that have a token after
next.

**Departures from the published description.** ``config.json`` gives widths
and counts and not the wiring: the block, the MTP module, the correction
bias's rule and the loss weight are the family's (arXiv:2412.19437), listed
under ``assumed`` in the configuration's file. ``W_ukv`` is kept as two
leaves. The row's last position reads the row's first token in
``t_{i+1}``'s place, which no other position sees, and is left out of the MTP
loss. What the experts held elsewhere would add is left out, here as in the
program.

**Choices handed in.** As ``reference/nemotron_h.py``: the comparison hands
this reference the program's choices of experts (``attach_choices``) and
``choice_margins`` holds them to this reference's own scores.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.matmul import make_einsum
from benchmark.reference.nemotron_h import attach_choices, balance  # noqa: F401
from benchmark.reference.xing4 import (MASKED, QUERY_BLOCK, cross_entropy,
                                       experts, mtp_cross_entropy, rms_norm)


def sizes_of(c: dict) -> dict:
    if c.get("rope_scaling"):
        raise ValueError("this reference turns by plain RoPE: rope_scaling "
                         "has to be null")
    if (c["n_group"], c["topk_group"]) != (1, 1):
        raise ValueError("this reference's router has no group limit")
    dep = c["deployment"]
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
            "layers": c["num_hidden_layers"],
            "dense": c["first_k_dense_replace"],
            "mtp": c["num_nextn_predict_layers"],
            "lam": c.get("mtp_loss_weight", 0.0)}


def blocks_of(z: dict) -> list:
    """``(prefix, routed)`` of every block in the order it runs: the trunk's
    layers, then the MTP module's one."""
    return ([(f"l{i}", i >= z["dense"]) for i in range(z["layers"])]
            + [("m", True)] * z["mtp"])


def routed_blocks(c: dict) -> int:
    return sum(routed for _, routed in blocks_of(sizes_of(c)))


def weight_spec(c: dict) -> dict:
    """Leaf -> ``(shape, kind)``, one leaf a layer and matrix (no stacks:
    ``reference/nemotron_h.py`` says why). Kinds are ``harness/weights.py``'s."""
    z = sizes_of(c)
    d, h = z["d"], z["h"]
    spec = {"wte": ((z["v"], d), "w"), "lnf.g": ((d,), "gain"),
            "head.w": ((d, z["v"]), "w")}
    shared = (("ln1.g", (d,), "gain"), ("ln2.g", (d,), "gain"),
              ("wdq", (d, z["qr"]), "w"), ("qn.g", (z["qr"],), "gain"),
              ("wuq", (z["qr"], h * (z["nope"] + z["rope"])), "w"),
              ("wdkv", (d, z["kvr"] + z["rope"]), "w"),
              ("kvn.g", (z["kvr"],), "gain"),
              ("wuk", (z["kvr"], h * z["nope"]), "w"),
              ("wuv", (z["kvr"], h * z["dv"]), "w"),
              ("wo", (h * z["dv"], d), "w"))
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


def rope(x, theta: float):
    """``x [S, heads, rope]`` turned at positions ``0..S-1``: pair ``i`` (the
    numbers ``2i`` and ``2i + 1`` of a head) by ``t theta^(-2i/rope)``."""
    width = x.shape[-1]
    inv = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention(u, p: dict, z: dict, einsum):
    """One row ``u [S, d]`` (normed): MLA expanded, by blocks of queries."""
    s = u.shape[0]
    h, nope, rp, dv = z["h"], z["nope"], z["rope"], z["dv"]
    cq = rms_norm(einsum("sd,dr->sr", u, p["wdq"]), p["qn.g"], z["eps"])
    q = einsum("sr,re->se", cq, p["wuq"]).reshape(s, h, nope + rp)
    down = einsum("sd,dr->sr", u, p["wdkv"])
    ckv = rms_norm(down[:, :z["kvr"]], p["kvn.g"], z["eps"])
    k_rope = rope(down[:, None, z["kvr"]:], z["theta"])[:, 0]     # [S, rope]
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], z["theta"])
    k_nope = einsum("sr,re->se", ckv, p["wuk"]).reshape(s, h, nope)
    v = einsum("sr,re->se", ckv, p["wuv"]).reshape(s, h, dv)
    scale = (nope + rp) ** -0.5
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


def forward(w: dict, tokens, c: dict, precision: str = "f32", choices=None,
            shift: int = 0, bias=None, found: list | None = None):
    """``(logits [B, S, V], the MTP module's logits [B, S, V] or None, the
    choice margins, the choices made [routed blocks, B, S, k], every routed
    block's loads [routed blocks, width])``. ``choices`` / ``shift`` / ``bias
    [routed blocks, width]`` / ``found`` as
    ``reference/nemotron_h.py::forward``; the routed blocks count the trunk's,
    then the MTP module's."""
    einsum = make_einsum(precision)
    z = sizes_of(c)
    b, s = tokens.shape
    margins, shares, made, loads = [], [], [], []

    def block(x, prefix, is_routed):
        p = {k.partition(".")[2]: v for k, v in w.items()
             if k.startswith(prefix + ".")}

        @jax.checkpoint
        def attend(x, p):
            return x + jax.lax.map(
                lambda row: attention(row, p, z, einsum),
                rms_norm(x, p["ln1.g"], z["eps"]))

        x = attend(x, p)
        if not is_routed:
            @jax.checkpoint
            def feed(x, p):
                u = rms_norm(x, p["ln2.g"], z["eps"])
                return x + einsum("bsf,fd->bsd", jax.nn.silu(einsum(
                    "bsd,df->bsf", u, p["wg"])) * einsum(
                        "bsd,df->bsf", u, p["wu"]), p["wd"])
            return feed(x, p)
        j = len(loads)
        given = None if choices is None else choices[j].reshape(b * s, -1)
        own_bias = None if bias is None else bias[j]
        if found is not None:
            u = rms_norm(x, p["ln2.g"], z["eps"]).reshape(b * s, -1)
            own_bias = balance(jax.nn.sigmoid(einsum(
                "td,de->te", u, p["router"])), z["k"])
            found.append(own_bias)

        @jax.checkpoint
        def feed(x, p, given, own_bias):
            u = rms_norm(x, p["ln2.g"], z["eps"]).reshape(b * s, -1)
            out, *rest = experts(u, p, z, einsum, given=given, shift=shift,
                                 bias=own_bias)
            return x + out.reshape(x.shape), rest

        x, ((off, missed), numbers, load) = feed(x, p, given, own_bias)
        margins.append(off), shares.append(missed), loads.append(load)
        made.append(numbers.reshape(b, s, -1))
        return x

    x = w["wte"][tokens]
    for prefix, is_routed in blocks_of(z)[:z["layers"]]:
        x = block(x, prefix, is_routed)
    head = lambda h: einsum("bsd,dv->bsv", rms_norm(            # noqa: E731
        h, w["lnf.g"], z["eps"]), w["head.w"])
    logits, ahead = head(x), None
    if z["mtp"]:
        joined = jnp.concatenate(
            [rms_norm(x, w["m.hn.g"], z["eps"]),
             rms_norm(w["wte"][jnp.roll(tokens, -1, axis=1)], w["m.en.g"],
                      z["eps"])], axis=-1)
        ahead = head(block(einsum("bse,ed->bsd", joined, w["m.weh"]), "m",
                           True))
    stack = lambda xs: jnp.stack(xs) if xs else jnp.zeros((0,))  # noqa: E731
    held_to = {"expert_choice_margin": jnp.max(stack(margins), initial=0.0),
               "experts_misplaced_share": jnp.sum(stack(shares))
               / max(len(shares), 1)}
    return logits, ahead, held_to, stack(made), stack(loads)


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
