"""``reference/optim.py``'s loop for a model whose float32 trees fill the chip.

The same steps, arithmetic and return value as ``optim.run_steps`` (it imports
that file's constants and ``leaf_norms``); what differs is what stays on the
device. There, at the moment of a step's update nine trees the size of the
weights are alive — the starting weights, the weights, both moments, the
summed gradient, the last block's gradient (still bound to its name) and the
update's three outputs. At Keye-VL-2.0's cut, 465 M parameters and 1.86 GB a
tree, that is 16.75 GB of a v5e's 16.9 (my chip runs, PR 32: it passed with
1.4 MB to spare and failed once the rows carried 0.2 GB of choices). Here the
update is given its moments and the gradient to write into, and a block's
gradient is let go once added: six trees and a block's temporaries at the
peak. Before it starts it also drops what the program left loaded: a loaded
executable keeps its scratch in device memory (1.7 GB after ``fit``, my chip
runs, PR 32), and the reference needs the room.
``families/lm_sparse_moe_train.py`` puts this loop in ``optim``'s place for
the length of its run; PERF.md section 7 asks a ``benchmark`` issue to give
``optim.run_steps`` the same changes, after which this file goes.

It also reads one number the harness's comparison has no place for. The
harness compares NORMS by leaf, and a norm moves with the square of an error
that is not aligned with the gradient: at this cell's size the float8 control
moves the worst leaf's norm by 0.2 to 0.5 %, which the sound runs reach (my
chip runs, PR 32). The DISTANCE between two gradients moves with the error
itself. So a run may be given another run's first gradient to hold its own
against (``hold_against``: the program's, which the family reads from Adam's
first moment; then this loop's own float32 one, for the controls), and
``DIRECTION_GAPS`` receives, a call, every leaf's ``|g - g'|`` over the
reference's norm of that leaf or of the median leaf, whichever is larger
(``check.norm_gap``'s floor).
"""

from __future__ import annotations

import gc
import statistics

import jax
import jax.numpy as jnp

from benchmark.reference.optim import B1, B2, EPS, leaf_norms


# another run's first gradient, host arrays under the reference's names, with
# the reference's norms by leaf once the held one IS the reference's; and what
# each call of run_steps since read against it: [(leaf, share)], worst first
_HELD: dict = {}
DIRECTION_GAPS: list = []


def hold_against(gradient: dict | None, keep_reference: bool = False):
    """The next ``run_steps`` (the float32 reference) holds its first gradient
    against ``gradient`` (the program's). With ``keep_reference`` its own then
    takes that place, and later calls (the controls) are held against it."""
    _HELD.clear()
    DIRECTION_GAPS.clear()
    if gradient is not None:
        _HELD.update(tree=gradient, norms=None, keep=keep_reference)


def shares(distances: dict, reference_norms: dict) -> list:
    """``[(leaf, distance over the reference's norm of that leaf or of the
    median leaf, whichever is larger)]``, the worst leaf first."""
    floor = statistics.median(reference_norms.values())
    return sorted(((k, d / max(reference_norms[k], floor))
                   for k, d in distances.items()), key=lambda x: -x[1])


def run_steps(loss_fn, weights: dict, batches: list, hyper: dict,
              micro: int, row_sharding=None) -> dict:
    jax.clear_caches()
    gc.collect()
    lr, wd = float(hyper["learning_rate"]), float(hyper["weight_decay"])
    grad_block = jax.jit(jax.value_and_grad(loss_fn))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)
    scale = jax.jit(lambda g, n: jax.tree.map(lambda g_: g_ / n, g),
                    donate_argnums=0)

    def adamw(w, m, v, g, t):
        m = jax.tree.map(lambda m_, g_: B1 * m_ + (1 - B1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: B2 * v_ + (1 - B2) * g_ * g_, v, g)
        c1, c2 = 1 - B1 ** t, 1 - B2 ** t
        w = jax.tree.map(
            lambda w_, m_, v_: w_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + EPS)
                                          + wd * w_), w, m, v)
        return w, m, v

    # the moments and the gradient are written over; the weights are not (the
    # first step's are the ones the change is measured from)
    adamw = jax.jit(adamw, donate_argnums=(1, 2, 3))
    norms = jax.jit(leaf_norms)
    distances = jax.jit(
        lambda g, a: leaf_norms(jax.tree.map(jnp.subtract, g, a)))
    w0 = w = weights
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
            loss = loss + float(l_b)
            grads = g_b if grads is None else add(grads, g_b)
            del g_b, block
        grads = scale(grads, jnp.float32(n_blocks))
        losses.append(loss / n_blocks)
        if t == 1:
            grad_norms = {k: float(x) for k, x in norms(grads).items()}
            if _HELD:
                off = distances(grads, _HELD["tree"])
                DIRECTION_GAPS.append(shares(
                    {k: float(x) for k, x in off.items()},
                    _HELD["norms"] or grad_norms))
                print("gradient held against another's, worst leaf first: "
                      + str([(k, float(f"{x:.3g}"))
                             for k, x in DIRECTION_GAPS[-1]]), flush=True)
                if _HELD["norms"] is None and _HELD["keep"]:
                    _HELD.update(tree=jax.device_get(grads), norms=grad_norms)
                elif _HELD["norms"] is None:
                    _HELD.clear()
        w, m, v = adamw(w, m, v, grads, jnp.float32(t))
        del grads
    delta = norms(jax.tree.map(jnp.subtract, w, w0))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": {k: float(x) for k, x in delta.items()}}
