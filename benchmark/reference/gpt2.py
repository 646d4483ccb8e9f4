"""GPT-2 (Radford et al. 2019) as plain ``jax.numpy``: forward, next-token
loss. Float32, no kernels, no cache, nothing of the program imported.

Follows the published block — learned positions, pre-LayerNorm, causal
multi-head attention scaled by 1/sqrt(head size), GELU (tanh form,
``gelu_new``), final LayerNorm — with the departures the configuration file
lists under ``changed``: an untied output head with a bias, and the LayerNorm
epsilon the program uses. Layers are stacked on a leading axis and scanned, one
``jax.checkpoint`` a block, so that the float32 activations of 24 layers are
not all kept.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.matmul import make_einsum


def weight_spec(sizes: dict) -> dict:
    d, f, v = sizes["n_embd"], sizes["n_inner"], sizes["vocab_size"]
    n, s = sizes["n_layer"], sizes["n_positions"]
    spec = {"wte": ((v, d), "w"), "wpe": ((s, d), "w"),
            "lnf.g": ((d,), "gain"), "lnf.b": ((d,), "bias"),
            "head.w": ((d, v), "w"), "head.b": ((v,), "bias")}
    for name, shape, kind in (
            ("ln1.g", (d,), "gain"), ("ln1.b", (d,), "bias"),
            ("attn.wq", (d, d), "w"), ("attn.bq", (d,), "bias"),
            ("attn.wk", (d, d), "w"), ("attn.bk", (d,), "bias"),
            ("attn.wv", (d, d), "w"), ("attn.bv", (d,), "bias"),
            ("attn.wo", (d, d), "w"), ("attn.bo", (d,), "bias"),
            ("ln2.g", (d,), "gain"), ("ln2.b", (d,), "bias"),
            ("fc1.w", (d, f), "w"), ("fc1.b", (f,), "bias"),
            ("fc2.w", (f, d), "w"), ("fc2.b", (d,), "bias")):
        spec["blk." + name] = ((n, *shape), kind)
    return spec


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(q, k, v, causal: bool, einsum):
    """q, k, v: [B, S, H, hd] -> [B, S, H, hd]."""
    scores = einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


def block(h, p: dict, heads: int, eps: float, causal: bool, einsum):
    """One pre-LN transformer block; ``p`` holds this layer's leaves under the
    names of ``weight_spec`` without the ``blk.`` prefix. ViT's reference uses
    it too (``causal=False``)."""
    b, s, d = h.shape
    a = layer_norm(h, p["ln1.g"], p["ln1.b"], eps)
    q, k, v = (
        (einsum("bsd,de->bse", a, p[f"attn.w{n}"]) + p[f"attn.b{n}"]
         ).reshape(b, s, heads, d // heads) for n in "qkv")
    o = attention(q, k, v, causal, einsum).reshape(b, s, d)
    h = h + einsum("bsd,de->bse", o, p["attn.wo"]) + p["attn.bo"]
    m = layer_norm(h, p["ln2.g"], p["ln2.b"], eps)
    m = gelu_tanh(einsum("bsd,df->bsf", m, p["fc1.w"]) + p["fc1.b"])
    return h + einsum("bsf,fd->bsd", m, p["fc2.w"]) + p["fc2.b"]


def scan_blocks(h, w: dict, heads: int, eps: float, causal: bool, einsum):
    stacked = {k[4:]: x for k, x in w.items() if k.startswith("blk.")}

    @jax.checkpoint
    def body(h, p):
        return block(h, p, heads, eps, causal, einsum), None

    return jax.lax.scan(body, h, stacked)[0]


def logits_fn(w: dict, tokens, sizes: dict, precision: str = "f32"):
    einsum = make_einsum(precision)
    eps = sizes["layer_norm_epsilon"]
    h = w["wte"][tokens] + w["wpe"][: tokens.shape[1]]
    h = scan_blocks(h, w, sizes["n_head"], eps, True, einsum)
    h = layer_norm(h, w["lnf.g"], w["lnf.b"], eps)
    return einsum("bsd,dv->bsv", h, w["head.w"]) + w["head.b"]


def make_loss(sizes: dict, precision: str = "f32"):
    def loss_fn(w, inputs, targets):
        logits = logits_fn(w, inputs, sizes, precision)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(picked)
    return loss_fn
