"""The plain reference's training loop: gradients by micro-batch, AdamW in
float32, and the norms the benchmark compares. Imports nothing of the program.

``loss_fn(weights, inputs, targets) -> mean loss over the rows`` comes from the
model's reference file. Rows go through in blocks of ``micro`` so that the
float32 activations of a whole timed batch never sit on the device together;
the mean over equal blocks of the blocks' means is the batch mean.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

B1, B2, EPS = 0.9, 0.999, 1e-8          # optax.adamw's defaults, restated


def leaf_norms(tree: dict, stacked_prefix: str = "blk.") -> dict:
    """L2 norm of every leaf; a leaf stacked over layers (its name starts with
    ``stacked_prefix``) gives one norm per layer, named ``<leaf>@<layer>``."""
    out = {}
    for name, x in tree.items():
        x = x.astype(jnp.float32)
        if name.startswith(stacked_prefix):
            per = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            for i in range(x.shape[0]):
                out[f"{name}@{i}"] = per[i]
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    return out


def run_steps(loss_fn, weights: dict, batches: list, hyper: dict,
              micro: int, row_sharding=None) -> dict:
    """Follow ``len(batches)`` optimizer steps from ``weights``.

    Returns the loss of each step, the per-leaf norm of the first step's
    gradient, and the per-leaf norm of the parameters' change after the last.
    ``row_sharding`` (optional) places each block of rows, over several chips.
    ``hyper``: ``learning_rate``, ``weight_decay`` (AdamW, decay on every leaf,
    as ``optax.adamw`` with no mask applies it).
    """
    lr, wd = float(hyper["learning_rate"]), float(hyper["weight_decay"])
    grad_block = jax.jit(jax.value_and_grad(loss_fn))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)

    @jax.jit
    def adamw(w, m, v, g, t):
        m = jax.tree.map(lambda m_, g_: B1 * m_ + (1 - B1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: B2 * v_ + (1 - B2) * g_ * g_, v, g)
        c1, c2 = 1 - B1 ** t, 1 - B2 ** t
        w = jax.tree.map(
            lambda w_, m_, v_: w_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + EPS)
                                          + wd * w_), w, m, v)
        return w, m, v

    norms = jax.jit(leaf_norms)
    w0 = weights
    w = weights
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad_norms = [], None
    for t, (inputs, targets) in enumerate(batches, start=1):
        rows = inputs.shape[0]
        if rows % micro:
            raise ValueError(f"{rows} rows do not split into blocks of {micro}")
        n_blocks = rows // micro
        loss, grads = 0.0, None
        for b in range(n_blocks):
            sl = slice(b * micro, (b + 1) * micro)
            block = (inputs[sl], targets[sl])
            if row_sharding is not None:
                block = jax.device_put(block, row_sharding)
            l_b, g_b = grad_block(w, *block)
            loss = loss + l_b
            grads = g_b if grads is None else add(grads, g_b)
        grads = jax.tree.map(lambda g_: g_ / n_blocks, grads)
        losses.append(float(loss) / n_blocks)
        if t == 1:
            grad_norms = {k: float(x) for k, x in norms(grads).items()}
        w, m, v = adamw(w, m, v, grads, jnp.float32(t))
    delta = norms(jax.tree.map(jnp.subtract, w, w0))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: float(x) for k, x in delta.items()}}
