"""``reference/optim.py``'s loop for a model of which four float32 trees are
most of the chip: the same steps, arithmetic and return value (it imports
that file's constants and ``leaf_norms``), with less kept on the device.

At Nemotron-3-Nano's cut a tree is 667 M parameters, 2.67 GB; ``optim.py``
holds nine at a step's update and ``optim_donating.py`` six, 16.0 GB of a
v5e's 16.9 before a row's activations. Here the starting weights wait on the
HOST (the parameters' change is read against them leaf by leaf at the end), a
block's gradient is added into the running mean inside the call that makes
it, the update writes over the weights, both moments and the gradient, and
between updates the moments wait on the host too: while a gradient is made
the device holds the weights, the running gradient and a block's temporaries
(9.8 GB by the compiler's plan at this size), at an update four trees.

Like ``optim_donating.py`` it drops what the program left loaded before it
starts, and it reads that file's one extra number the same way: with
``optim_donating.hold_against`` given another run's first gradient, its
``DIRECTION_GAPS`` receives every leaf's ``|g - g'|`` over the reference's
norm of that leaf or of the median leaf, whichever is larger — here leaf by
leaf from the host, so that the held gradient is never a fifth tree.
``families/lm_hybrid_ssm_moe_train.py`` puts this loop in ``optim``'s place
for the length of its run.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import optim_donating
from benchmark.reference.optim import B1, B2, EPS, leaf_norms


def run_steps(loss_fn, weights: dict, batches: list, hyper: dict,
              micro: int, row_sharding=None) -> dict:
    jax.clear_caches()
    gc.collect()
    lr, wd = float(hyper["learning_rate"]), float(hyper["weight_decay"])

    def into(w, mean, n, inputs, targets):
        """A block's loss, and the running mean of the gradient with the
        block's share added."""
        value, g = jax.value_and_grad(loss_fn)(w, inputs, targets)
        return value, jax.tree.map(lambda m_, g_: m_ + g_ / n, mean, g)

    into = jax.jit(into, donate_argnums=1)

    def adamw(w, m, v, g, t):
        m = jax.tree.map(lambda m_, g_: B1 * m_ + (1 - B1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: B2 * v_ + (1 - B2) * g_ * g_, v, g)
        c1, c2 = 1 - B1 ** t, 1 - B2 ** t
        w = jax.tree.map(
            lambda w_, m_, v_: w_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + EPS)
                                          + wd * w_), w, m, v)
        return w, m, v

    # everything is written over: the starting weights are on the host
    adamw = jax.jit(adamw, donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(leaf_norms)
    apart = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))
    whole = next(iter(weights.values())).sharding
    place = lambda x: jax.device_put(x, whole)              # noqa: E731

    def apart_from_host(tree: dict, host: dict) -> dict:
        """``leaf_norms(tree - host)``, one leaf on the device at a time."""
        out: dict = {}
        for name, leaf in tree.items():
            out.update({k: float(x) for k, x in apart(
                {name: leaf}, {name: place(host[name])}).items()})
        return out

    start = jax.device_get(weights)
    w = weights
    m = v = {k: np.zeros(x.shape, np.float32) for k, x in w.items()}
    held = optim_donating._HELD
    losses, grad_norms = [], None
    for t, (inputs, targets) in enumerate(batches, start=1):
        rows = inputs.shape[0]
        if rows % micro:
            raise ValueError(f"{rows} rows do not split into blocks of {micro}")
        n_blocks = rows // micro
        loss, grads = 0.0, jax.tree.map(jnp.zeros_like, w)
        for b in range(n_blocks):
            sl = slice(b * micro, (b + 1) * micro)
            block = (inputs[sl], targets[sl])
            if row_sharding is not None:
                block = jax.device_put(block, row_sharding)
            l_b, grads = into(w, grads, jnp.float32(n_blocks), *block)
            loss = loss + float(l_b)
            del block
        losses.append(loss / n_blocks)
        if t == 1:
            grad_norms = {k: float(x) for k, x in norms(grads).items()}
            if held:
                optim_donating.DIRECTION_GAPS.append(optim_donating.shares(
                    apart_from_host(grads, held["tree"]),
                    held["norms"] or grad_norms))
                print("gradient held against another's, worst leaf first: "
                      + str([(k, float(f"{x:.3g}")) for k, x in
                             optim_donating.DIRECTION_GAPS[-1][:12]]),
                      flush=True)
                if held["norms"] is None and held["keep"]:
                    held.update(tree=jax.device_get(grads), norms=grad_norms)
                elif held["norms"] is None:
                    held.clear()
        w, m, v = adamw(w, place(m), place(v), grads, jnp.float32(t))
        del grads
        if t < len(batches):
            m, v = jax.device_get((m, v))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": apart_from_host(w, start)}
