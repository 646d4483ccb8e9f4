"""ViT (Dosovitskiy et al. 2020) as plain ``jax.numpy``: patch embedding,
learned positions, pre-LayerNorm encoder blocks, final LayerNorm, a linear
classifier; softmax cross-entropy. Float32, no kernels, nothing of the program
imported. The block is the one ``reference/gpt2.py`` defines, without the
causal mask.

Departures from the published model, listed in the configuration file under
``changed``: no class token — the classifier reads the mean over the patch
tokens, as ``models/vit.py`` does; GELU in its tanh form; the program's
LayerNorm epsilon.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import layer_norm, scan_blocks
from benchmark.reference.matmul import make_einsum


def weight_spec(sizes: dict) -> dict:
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    n, p = sizes["num_hidden_layers"], sizes["patch_size"]
    tokens = (sizes["image_size"] // p) ** 2
    spec = {"patch.w": ((p * p * sizes["num_channels"], d), "w"),
            "patch.b": ((d,), "bias"), "pos": ((tokens, d), "w"),
            "lnf.g": ((d,), "gain"), "lnf.b": ((d,), "bias"),
            "head.w": ((d, sizes["num_labels"]), "w"),
            "head.b": ((sizes["num_labels"],), "bias")}
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


def patches(images, p: int):
    """[B, H, W, C] -> [B, (H/p)(W/p), p*p*C], a patch's numbers in (row,
    column, channel) order — the order of an HWIO convolution kernel."""
    b, h, w, c = images.shape
    x = images.reshape(b, h // p, p, w // p, p, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p),
                                                 p * p * c)


def logits_fn(w: dict, images, sizes: dict, precision: str = "f32"):
    einsum = make_einsum(precision)
    eps = sizes["layer_norm_eps"]
    x = patches(images.astype(jnp.float32), sizes["patch_size"])
    h = einsum("bsk,kd->bsd", x, w["patch.w"]) + w["patch.b"] + w["pos"]
    h = scan_blocks(h, w, sizes["num_attention_heads"], eps, False, einsum)
    h = layer_norm(h, w["lnf.g"], w["lnf.b"], eps)
    return einsum("bd,dc->bc", jnp.mean(h, axis=1), w["head.w"]) + w["head.b"]


def make_loss(sizes: dict, precision: str = "f32"):
    def loss_fn(w, images, labels):
        logp = jax.nn.log_softmax(logits_fn(w, images, sizes, precision), -1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return loss_fn
