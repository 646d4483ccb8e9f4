"""The looped decoder of Ouro (``model_type`` ``ouro``, arXiv:2510.25741) as
plain ``jax.numpy``: forward, the four exits, the gate, the expected loss with
its entropy term and, through ``jax.grad``, gradients. Float32,
``Precision.HIGHEST``, no kernels, no cache, nothing of the program imported.
Written from the layers' equations (ISSUE 48; docs/layer_spec.md), with the
configuration file's keys (``configs/ouro-2.6b.json``).

A block, sandwich-normed (a norm before AND after each sublayer):

    x <- x + RMSNorm_2(Attn(RMSNorm_1(x)));  x <- x + RMSNorm_4(SwiGLU(RMSNorm_3(x)))

``Attn``: ``q, k, v = h W_q, h W_k, h W_v`` as ``num_attention_heads`` heads of
``head_dim``, no biases, no q/k norm; q and k turned by RoPE (``rope_theta``,
the whole head, positions as they are); scores ``q . k head_dim^-1/2``, causal,
softmax; ``concat(P v) W_o`` — with the ``[S, S]`` scores, by blocks of
queries. ``SwiGLU(h) = (silu(h W_gate) * (h W_up)) W_down``.

The loop: ``h_0 = Emb(tokens)``; for ``t = 1 .. total_ut_steps``: ``h_t =
RMSNorm_f(Stack(h_{t-1}))``, ``Stack`` the layers in order, THE SAME WEIGHTS in
every pass: a Python loop that reads one weight dictionary. Exit ``t`` has
logits ``h_t W_head`` and a gate ``lam_t = sigmoid(h_t w_g + b_g)`` a token.

The loss: ``p_1 = lam_1``, ``p_t = lam_t prod_{j<t} (1 - lam_j)``, the last
exit takes what is left, ``prod_{j<T} (1 - lam_j)``; ``L = mean over tokens of
[sum_t p_t CE_t - beta H(p)]``, ``H`` the entropy of a token's ``p``, ``beta``
the configuration's ``exit_entropy_weight``.

**Departures from the published description.** ``config.json`` gives widths
and counts and not the wiring: that the final norm's output feeds the next
pass, the gate's form (one ``hidden -> 1`` projection with a bias on the
normed state, shared by the passes), ``beta`` and the absence of biases are
listed under ``assumed`` in the configuration's file. RoPE turns interleaved
pairs (``2i``, ``2i + 1``) where the family's published code turns the two
halves of a head: a permutation of a head's columns, the same model at seeded
weights. ``0 ln 0`` is taken as 0 (a ``p`` is clamped at 1e-30 inside the
logarithm). A sublayer is wrapped in ``jax.checkpoint`` and the head is taken
in chunks of tokens so that a row of 8,192 tokens over 49,152 ids fits a chip
in float32 (the gradient block plans 14.8 GiB with the compiler's own
rematerialisation and ran, my chip runs, PR 48); neither changes a product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.joyai_flash import rope
from benchmark.reference.matmul import make_einsum
from benchmark.reference.xing4 import MASKED, QUERY_BLOCK, rms_norm

HEAD_CHUNK = 1024   # tokens whose logits exist at a time; no result depends
                    # on it
LEAVES = ("ln1.g", "ln2.g", "ln3.g", "ln4.g", "wq", "wk", "wv", "wo", "wg",
          "wu", "wd")


def sizes_of(c: dict) -> dict:
    if c.get("rope_scaling"):
        raise ValueError("this reference turns by plain RoPE: rope_scaling "
                         "has to be null")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("this reference has one key head a query head")
    return {"d": c["hidden_size"], "h": c["num_attention_heads"],
            "hd": c["head_dim"], "f": c["intermediate_size"],
            "v": c["vocab_size"], "eps": c["rms_norm_eps"],
            "theta": c["rope_theta"], "layers": c["num_hidden_layers"],
            "passes": c["total_ut_steps"],
            "beta": c.get("exit_entropy_weight", 0.0)}


def weight_spec(c: dict) -> dict:
    """Leaf -> ``(shape, kind)``, one leaf a layer and matrix (no stacks:
    ``reference/nemotron_h.py`` says why). Kinds are ``harness/weights.py``'s."""
    z = sizes_of(c)
    d, e = z["d"], z["h"] * z["hd"]
    spec = {"wte": ((z["v"], d), "w"), "lnf.g": ((d,), "gain"),
            "head.w": ((d, z["v"]), "w"), "gate.w": ((d, 1), "w"),
            "gate.b": ((1,), "bias")}
    shapes = {"wq": (d, e), "wk": (d, e), "wv": (d, e), "wo": (e, d),
              "wg": (d, z["f"]), "wu": (d, z["f"]), "wd": (z["f"], d)}
    for i in range(z["layers"]):
        for name in LEAVES:
            spec[f"l{i}.{name}"] = ((shapes[name], "w") if name in shapes
                                    else ((d,), "gain"))
    return spec


def attention(u, p: dict, z: dict, einsum):
    """One row ``u [S, d]`` (normed): the ``[S, S]`` scores by blocks of
    queries."""
    s, h, hd = u.shape[0], z["h"], z["hd"]
    q = rope(einsum("sd,de->se", u, p["wq"]).reshape(s, h, hd), z["theta"])
    k = rope(einsum("sd,de->se", u, p["wk"]).reshape(s, h, hd), z["theta"])
    v = einsum("sd,de->se", u, p["wv"]).reshape(s, h, hd)
    tile = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    pos = jnp.arange(s)

    @jax.checkpoint
    def query_block(args):
        q_b, t_b = args
        scores = einsum("qhd,khd->hqk", q_b, k) * hd ** -0.5
        causal = pos[None, :] <= t_b[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, MASKED), axis=-1)
        return einsum("hqk,khd->qhd", probs, v).reshape(tile, h * hd)

    cut = lambda t: t.reshape(s // tile, tile, *t.shape[1:])   # noqa: E731
    out = jax.lax.map(query_block, (cut(q), cut(pos)))
    return einsum("se,ed->sd", out.reshape(s, h * hd), p["wo"])


def block(x, p: dict, z: dict, einsum):
    """One sandwich-normed layer on ``x [B, S, d]``."""
    @jax.checkpoint
    def attend(x, p):
        out = jax.lax.map(lambda row: attention(row, p, z, einsum),
                          rms_norm(x, p["ln1.g"], z["eps"]))
        return x + rms_norm(out, p["ln2.g"], z["eps"])

    @jax.checkpoint
    def feed(x, p):
        u = rms_norm(x, p["ln3.g"], z["eps"])
        out = einsum("bsf,fd->bsd", jax.nn.silu(einsum(
            "bsd,df->bsf", u, p["wg"])) * einsum("bsd,df->bsf", u, p["wu"]),
            p["wd"])
        return x + rms_norm(out, p["ln4.g"], z["eps"])

    return feed(attend(x, p), p)


def token_cross_entropy(h, head, targets, einsum):
    """``[B, S]``: every token's cross-entropy through the head, ``HEAD_CHUNK``
    tokens' logits at a time (made again in the backward pass)."""
    b, s, d = h.shape
    n = b * s
    chunk = HEAD_CHUNK if n % HEAD_CHUNK == 0 else n

    @jax.checkpoint
    def some(args):
        h_c, t_c = args
        logp = jax.nn.log_softmax(einsum("td,dv->tv", h_c, head), axis=-1)
        return -jnp.take_along_axis(logp, t_c[:, None], axis=-1)[:, 0]

    return jax.lax.map(some, (h.reshape(n // chunk, chunk, d),
                              targets.reshape(n // chunk, chunk))
                       ).reshape(b, s)


def exit_distribution(lam):
    """``lam [T, ...]`` -> ``p [T, ...]`` by the recurrence of the module's
    docstring: the last exit takes what is left (its own gate is not read)."""
    left = jnp.ones_like(lam[0])
    out = []
    for t in range(lam.shape[0] - 1):
        out.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(out + [left])


def exits(w: dict, tokens, targets, c: dict, precision: str = "f32"):
    """``(ce [T, B, S], p [T, B, S])``: every exit's cross-entropy a token and
    the token's distribution over the exits."""
    einsum = make_einsum(precision)
    z = sizes_of(c)
    layers = [{name: w[f"l{i}.{name}"] for name in LEAVES}
              for i in range(z["layers"])]
    h = w["wte"][tokens]
    ce, lam = [], []
    for _ in range(z["passes"]):
        for p in layers:
            h = block(h, p, z, einsum)
        h = rms_norm(h, w["lnf.g"], z["eps"])
        ce.append(token_cross_entropy(h, w["head.w"], targets, einsum))
        lam.append(jax.nn.sigmoid(
            einsum("bsd,do->bso", h, w["gate.w"])[..., 0] + w["gate.b"][0]))
    return jnp.stack(ce), exit_distribution(jnp.stack(lam))


def terms(w: dict, tokens, targets, c: dict,
          precision: str = "f32") -> dict:
    """The loss and what the program counts beside it: ``total`` (what is
    descended), ``last`` (the last exit's mean cross-entropy, which the
    program reports as ``loss``), ``exit_loss`` and ``exit_share`` (``[T]``:
    the means of ``CE_t`` and ``p_t``), ``exit_entropy`` (mean ``H(p)``),
    ``exit_expected_passes`` (mean ``sum_t t p_t``)."""
    ce, p = exits(w, tokens, targets, c, precision)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    expected = jnp.sum(p * ce, axis=0)
    ranks = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
    return {"total": jnp.mean(expected - sizes_of(c)["beta"] * entropy),
            "last": jnp.mean(ce[-1]),
            "exit_loss": jnp.mean(ce, axis=(1, 2)),
            "exit_share": jnp.mean(p, axis=(1, 2)),
            "exit_entropy": jnp.mean(entropy),
            "exit_expected_passes": jnp.mean(jnp.tensordot(ranks, p, axes=1))}


def make_loss(c: dict, precision: str = "f32"):
    """What the benchmark's reference loop differentiates: its value is the
    LAST exit's cross-entropy, which is what the program reports as ``loss``,
    and its gradient that of the expected loss with its entropy term, which
    is what the program descends."""
    def loss_fn(w, inputs, targets):
        got = terms(w, inputs, targets, c, precision)
        return got["total"] + jax.lax.stop_gradient(got["last"]
                                                    - got["total"])
    return loss_fn
