"""Mixture-of-Experts MLPs: Switch top-1 / GShard top-2 routing with a capacity
and expert parallelism (``MoEMlp``), and top-k of a deployment's experts with
none dropped on the share a chip holds (``RoutedExperts``). Both route with
``route_topk`` and, where the experts are local, run them through one sorted,
grouped dispatch (``grouped_experts``).

Not a reference-parity item (the reference has no MoE — SURVEY.md §2d covers
DP/trial/HPO/batch-inference parallelism only); this is the expert-parallel
axis of the framework, same tier as TP (``parallel/sharding.py``) and SP
(``parallel/ring_attention.py``).

TPU-first formulation (Switch Transformer, Fedus et al. 2101.03961; GShard,
Lepikhin et al. 2006.16668):

- **token-choice routing** (``router="top1"`` Switch, ``router="top2"``
  GShard with renormalized pair gates) with a *static* per-expert capacity
  ``C = ceil(cf * k * T / E)``; tokens past capacity fall through the
  residual connection (standard Switch/GShard semantics, first choices
  claiming capacity before second). Local experts take the kept assignments
  sorted by expert (``grouped_experts``); only the all-to-all exchange, which
  needs equal blocks an expert, builds the dense ``[T, E, C]`` dispatch and
  combine tensors (``one_hot_dispatch``).
- **expert parallelism** over a named mesh axis: tokens stay sharded by the
  enclosing data/seq axes; each rank routes its local tokens against ALL ``E``
  experts, one ``lax.all_to_all`` ships the per-expert token blocks to the
  expert's owner rank, the owner applies its ``E_local = E / n`` expert FFNs,
  and a second ``all_to_all`` ships results back. The two all_to_alls ride ICI
  — this is THE canonical EP communication pattern.
- expert weights live as stacked tensors ``[E, D, H]`` (einsum over the expert
  dim hits the MXU batched); under EP each rank slices its own ``E_local``
  experts at apply time, so the parameter tree is identical with and without
  the axis (checkpoints are layout-stable; pair with ZeRO-1
  (``parallel/zero.py``) to shard the optimizer moments).
- the Switch **load-balance auxiliary loss** ``E * Σ_e f_e · p_e`` is sown
  under ``("intermediates", "moe_aux_loss")``; the LM train step adds it with
  coefficient ``aux_loss_weight`` when the model routes.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.lax import axis_size


def collect_sown(mods: dict, name: str) -> list:
    """Every value sown under ``name`` anywhere in an ``intermediates``
    collection (flax stores sows as tuples). MoE blocks sow several keys
    (aux loss, routing telemetry, raw gate logits) — consumers MUST select by
    name rather than summing all leaves, or telemetry leaks into the loss."""
    from flax import traverse_util

    flat = traverse_util.flatten_dict(mods.get("intermediates", mods))
    return [x for path, leaf in flat.items() if name in path
            for x in (leaf if isinstance(leaf, (tuple, list)) else (leaf,))]


def chosen(ranked: jnp.ndarray, k: int) -> jnp.ndarray:
    """The ``k`` largest of ``ranked [T, E]`` a token, the lower-numbered of
    equals: ``[T, k]`` indices, named ``expert_choice`` (the sigmoid router's
    choice; :func:`route_topk` says why the softmax router's is not). A block
    rematerialised whole keeps them (``models/lm.py``'s ``remat="full"``): a
    choice is not continuous in the scores, and a backward pass that made it
    again could, where two scores all but tie and the compiler rounds the
    second making otherwise, choose another expert than the forward pass did.
    While the sort was made again from it, that laid the backward pass's rows
    one off against the kept ``expert_hidden`` (v5e, 16,384 tokens, 4 layers
    of 128 experts top-6: the first step of two seeds in some thirty, and
    that layer's experts' gradient then missed by its own length; PERF.md
    section 6, PR 34). Since PR 36 the sort itself is kept
    (:func:`sort_by_expert`); the kept choice still says which score a row's
    gate is."""
    _, top_i = lax.top_k(lax.stop_gradient(ranked), k)
    return checkpoint_name(top_i, "expert_choice")


def route_topk(gate_logits: jnp.ndarray, k: int, normalise: bool):
    """Softmax over every expert the router scores, then the ``k`` largest:
    ``(weights [T, k], experts [T, k], probs [T, E])``, weights renormalised
    to sum to one where asked. Float32 throughout. This choice is NOT kept
    across a block's rematerialisation as :func:`chosen`'s is: kept, with
    the weights gathered by it, the one benchmark cell that routes so lost
    1.0 % of its rate for no reason found yet (PERF.md section 7, PR 34).
    The rows' order is kept (:func:`sort_by_expert`), so a second making
    that differs at a near tie costs one row its gate's last digits and no
    longer the layer its gradient."""
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    top_p, top_i = lax.top_k(probs, k)
    if normalise:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_i, probs


def route_sigmoid(gate_logits: jnp.ndarray, k: int, normalise: bool,
                  bias=None, scale: float = 1.0):
    """A sigmoid an expert in place of the softmax (DeepSeek-V3,
    arXiv:2412.19437): the ``k`` experts of largest ``score + bias`` are
    chosen, the lower-numbered of equals; the weights are the chosen SCORES
    (the bias steers the choice and nothing else), renormalised to sum to one
    where asked, times ``scale``. Same returns as :func:`route_topk`."""
    scores = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    ranked = scores if bias is None else scores + bias.astype(jnp.float32)
    top_i = chosen(ranked, k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if normalise:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    return top_s * scale, top_i, scores


def step_router_bias(buffers: dict, loads: dict, rate: float, mean=None):
    """The correction biases after a step: ``b_e + rate * sign(mean load -
    load_e)`` over every expert the router scores. ``loads``: what the
    routers sowed on this step's tokens as ``router_load``, by flattened path
    (a layer's lies beside the ``router_bias`` it read). ``mean`` (optional)
    averages a load over the chips that saw other tokens of the step, so
    that their biases stay one. No gradient reaches a bias and the optimizer
    never sees one."""
    from flax import traverse_util

    out = {}
    for path, bias in traverse_util.flatten_dict(buffers).items():
        load = loads[path[:-1] + ("router_load",)][0]
        load = mean(load) if mean is not None else load
        out[path] = bias + rate * jnp.sign(jnp.mean(load) - load)
    return traverse_util.unflatten_dict(out)


def capacity_routing(gate_logits: jnp.ndarray, capacity: int, k: int):
    """Token-choice routing with a static capacity an expert: Switch for
    ``k = 1`` (the gate is the chosen expert's probability), GShard for
    ``k = 2`` (gates renormalised over the pair). First choices claim an
    expert's capacity before second choices, arrival order within a choice.

    ``gate_logits`` [T, E] -> ``(weights [T, k], experts [T, k], keep [T, k],
    pos [T, k], aux, stats)``: ``pos`` is an assignment's place in its
    expert's queue, ``keep`` whether that lies under ``capacity`` (the others
    skip the expert; the caller's residual carries them).

    ``aux`` is the Switch/GShard balance term over FIRST choices,
    ``E * sum_e f_e * p_e``. ``stats`` (scalars but ``expert_frac`` [E]):
    ``drop_rate`` — assignments past capacity over ``k * T``;
    ``balance_entropy`` — entropy of the assignment distribution over
    ``log E`` (1.0 balanced, 0.0 collapsed onto one expert).
    """
    t, e = gate_logits.shape
    weights, experts, probs = route_topk(gate_logits, k, normalise=k > 1)
    claimed = jnp.zeros((e,), probs.dtype)      # capacity earlier choices took
    pos = []
    for choice in range(k):
        onehot = jax.nn.one_hot(experts[:, choice], e, dtype=probs.dtype)
        pos.append(jnp.sum((jnp.cumsum(onehot, axis=0) - 1.0) * onehot,
                           axis=-1) + onehot @ claimed)
        if choice == 0:
            aux = e * jnp.sum(jnp.mean(onehot, axis=0)
                              * jnp.mean(probs, axis=0))
        claimed = claimed + jnp.sum(onehot, axis=0)
    pos = jnp.stack(pos, axis=1).astype(jnp.int32)
    keep = pos < capacity
    frac = claimed / (k * t)
    stats = {
        "drop_rate": 1.0 - jnp.mean(keep.astype(probs.dtype)),
        "balance_entropy": (-jnp.sum(frac * jnp.log(frac + 1e-9))
                            / jnp.log(float(e))),
        "expert_frac": frac,
    }
    return weights, experts, keep, pos, aux, stats


def dense_dispatch(weights, experts, keep, pos, num_experts: int,
                   capacity: int, dtype):
    """A routing as dense tensors: ``(dispatch [T, E, C]`` one-hot, ``combine
    [T, E, C]`` gate-weighted``)``, an assignment past capacity an all-zero
    row. ``T * E * C`` numbers: what the expert-parallel exchange ships (equal
    blocks an expert) and the capacity sweep reads; local experts do not go
    through it."""
    placed = (jax.nn.one_hot(experts, num_experts, dtype=dtype)
              * keep[..., None].astype(dtype))[..., None] \
        * jax.nn.one_hot(pos, capacity, dtype=dtype)[:, :, None, :]
    return (jnp.sum(placed, axis=1),
            jnp.sum(placed * weights.astype(dtype)[:, :, None, None], axis=1))


def one_hot_dispatch(gate_logits: jnp.ndarray, capacity: int, k: int):
    """:func:`capacity_routing` through :func:`dense_dispatch`: ``(dispatch,
    combine, aux, stats)``."""
    weights, experts, keep, pos, aux, stats = capacity_routing(
        gate_logits, capacity, k)
    return (*dense_dispatch(weights, experts, keep, pos,
                            gate_logits.shape[1], capacity, weights.dtype),
            aux, stats)


def top1_routing(gate_logits: jnp.ndarray, capacity: int):
    """Switch top-1: :func:`one_hot_dispatch` with one choice a token."""
    return one_hot_dispatch(gate_logits, capacity, 1)


def top2_routing(gate_logits: jnp.ndarray, capacity: int):
    """GShard top-2: :func:`one_hot_dispatch` with two choices a token."""
    return one_hot_dispatch(gate_logits, capacity, 2)


def router_fn(router: str):
    """(dense routing fn, choices-per-token k) for a router name — the one
    place that maps names to semantics (MoEMlp and the characterization sweep
    both resolve through it, so they cannot diverge)."""
    if router == "top1":
        return top1_routing, 1
    if router == "top2":
        return top2_routing, 2
    raise ValueError(f"unknown router {router!r}; use 'top1' or 'top2'")


def expert_capacity(cf: float, k: int, tokens: int, experts: int) -> int:
    """Static per-expert capacity ``ceil(cf * k * T / E)`` (>= 1)."""
    return max(1, int(-(-cf * k * tokens // experts)))


def load_counts(loads: jnp.ndarray, wanted, tokens: int) -> dict:
    """What both layers sow as ``moe_counts``: ``loads [E]`` the assignments
    each local expert was given, ``wanted`` the assignments that asked for
    one."""
    given = jnp.sum(loads).astype(jnp.float32)
    return {"assignments_per_token": given / tokens,
            "load_max_over_mean": (jnp.max(loads) * loads.shape[0]
                                   / jnp.maximum(given, 1.0)),
            "dropped": jnp.asarray(wanted, jnp.float32) - given}


def sort_by_expert(local: jnp.ndarray, held: int):
    """``local [T, k]``: each assignment's index among the experts held here,
    or anything outside ``[0, held)`` for one that is not to be run here (an
    expert that lives elsewhere, an assignment past capacity). Returns
    ``order [T*k]`` (assignments sorted by held expert, the others last),
    ``slot [T*k]`` (where each assignment landed in that order: the inverse
    permutation, flat, since a ``[T, k]`` array of int32 is stored ``k`` to a
    tile's 128 lanes) and ``group_sizes [held]``, so that the rows of the
    order that hold an assignment to a held expert are the first
    ``sum(group_sizes)``. Named ``expert_sort``: a block rematerialised whole
    keeps the three beside the first product whose rows lie in that order
    (``models/lm.py``'s ``remat="full"``; 0.8 MB a layer at 16,384 tokens
    top-6), and its backward pass runs the chunks the forward pass ran
    whatever a second making of the router would say."""
    flat = local.reshape(-1)
    here = (flat >= 0) & (flat < held)
    key = jnp.where(here, flat, held)
    order = jnp.argsort(key, stable=True)
    slot = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(held, dtype=key.dtype)[None], axis=0,
        dtype=jnp.int32)
    return checkpoint_name((order, slot, group_sizes), "expert_sort")


GROUPED_TILE = 512


def grouped_pad(width: int) -> int:
    """Columns of zeros that bring an expert's hidden width to the next
    multiple of the grouped products' tile, where that is cheap (an eighth of
    the width at most). The compiler's grouped-matmul kernel runs a width of
    1,856 at half the rate of 2,048 (v5e, 8 experts 2688 x F, 9,216 rows,
    forward and backward: 23.85 ms against 13.03; 1,920 buys nothing: my chip
    runs, PR 34), and the grouped products are the part of a step whose time
    follows the routing."""
    pad = -width % GROUPED_TILE
    return pad if 0 < pad <= width // 8 else 0


def chunk_rows(rows: int, held: int, width: int) -> int:
    """Rows a chunk of the dispatch takes (:func:`grouped_experts`), from
    shapes alone: twice the load ``rows * held / width`` a router that is
    indifferent between its ``width`` experts puts on the ``held`` ones, in
    whole tiles of the grouped products, and the whole buffer at most (a
    layer that holds every expert its router scores runs one chunk)."""
    twice = -(-2 * rows * held // width)
    return min(rows, -(-twice // GROUPED_TILE) * GROUPED_TILE)


def expert_act(h, act: str):
    """An expert's activation on its first product's rows: gated
    (``swiglu``: the gate's half times the up projection's), squared
    (``relu2``) or ``gelu``."""
    if act == "swiglu":
        h_gate, h_up = jnp.split(h, 2, axis=-1)
        return nn.silu(h_gate) * h_up
    if act == "relu2":
        return jnp.square(nn.relu(h))
    return nn.gelu(h)


def _chunk(i, rows, order, group_sizes):
    """Chunk ``i`` of the sorted order, rows ``[i * rows, (i + 1) * rows)``:
    the assignments there (``order``'s entries), the groups' sizes clipped to
    that window, which rows hold an assignment to a held expert ``[rows, 1]``,
    and each such row's held expert."""
    lo = i * rows
    ends = jnp.cumsum(group_sizes)
    sizes = (jnp.clip(ends, lo, lo + rows)
             - jnp.clip(ends - group_sizes, lo, lo + rows))
    at = lo + jnp.arange(rows, dtype=ends.dtype)
    expert = jnp.minimum(jnp.sum(at[:, None] >= ends[None], axis=1),
                         group_sizes.shape[0] - 1)
    return (lax.dynamic_slice_in_dim(order, lo, rows), sizes,
            (at < ends[-1])[:, None], expert)


def _chunks(order, group_sizes, rows):
    """``order`` padded to whole chunks of ``rows``, and how many chunks hold
    an assignment to a held expert: the loops' trip count, read on the
    device."""
    pad = -order.shape[0] % rows
    return jnp.pad(order, (0, pad)), -(-jnp.sum(group_sizes) // rows)


SCATTERED_ROW = 2


def _to_tokens(buf, order, slot, chunks, rows, k):
    """The buffer's rows back in token order, ``[T, D]``: each token the sum
    of its ``k`` rows, a row no chunk wrote being zero. Two ways, chosen on
    the device by how much of the buffer ran: a gather through ``slot`` over
    all ``k * T`` slots, whose time is the buffer's whatever the routing, or
    a scatter-add of the chunks that ran, whose time follows the routing and
    which costs :data:`SCATTERED_ROW` gathered slots a row (v5e, rows of
    2,688 and 2,048 bfloat16: 0.32 us a scattered row, 0.155 a gathered
    slot; my chip runs, PR 36): the scatter-add while under half the buffer
    ran."""
    def by_slot(buf):
        return jnp.sum(buf[slot.reshape(-1, k)], axis=1)

    def by_row(buf):
        def add(i, acc):
            picked = lax.dynamic_slice_in_dim(order, i * rows, rows)
            return acc.at[picked // k].add(lax.dynamic_slice_in_dim(
                buf, i * rows, rows).astype(jnp.float32))

        return lax.fori_loop(0, chunks, add, jnp.zeros(
            (slot.shape[0] // k, buf.shape[1]), jnp.float32)).astype(buf.dtype)

    return lax.cond(SCATTERED_ROW * chunks * rows < slot.shape[0], by_row,
                    by_slot, buf)


def _dispatch_fwd_loop(act, rows, keep, xt, gates, w_first, w_down, b_in,
                       b_down, order, slot, group_sizes):
    k = gates.shape[1]
    order, chunks = _chunks(order, group_sizes, rows)
    flat_gates = gates.reshape(-1, 1)

    def body(i, carry):
        picked, sizes, mask, expert = _chunk(i, rows, order, group_sizes)
        # rows past the assignments to run are whatever the gather left
        # there: selected away on both sides of the products, never
        # multiplied, so nothing they hold reaches a sum or a gradient
        x = jnp.where(mask, xt[picked // k], 0)
        h = jnp.where(mask, lax.ragged_dot(x, w_first, sizes), 0)
        if keep:
            carry = (carry[0], lax.dynamic_update_slice_in_dim(
                carry[1], h, i * rows, 0))
        if b_in is not None:
            h = h + b_in[expert]
        gate = jnp.where(mask, flat_gates[picked], 0)
        y = lax.ragged_dot(jnp.where(mask, expert_act(h, act) * gate, 0),
                           w_down, sizes)
        if b_down is not None:
            y = y + gate * b_down[expert]
        y = jnp.where(mask, y, 0)
        return (lax.dynamic_update_slice_in_dim(carry[0], y, i * rows, 0),
                *carry[1:])

    init = (jnp.zeros((order.shape[0], xt.shape[1]), xt.dtype),)
    if keep:
        init += (jnp.zeros((order.shape[0], w_first.shape[-1]), xt.dtype),)
    y, *hidden = lax.fori_loop(0, chunks, body, init)
    return (_to_tokens(y, order, slot, chunks, rows, k), chunks), hidden


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _dispatch(act, rows, xt, gates, w_first, w_down, b_in, b_down, order,
              slot, group_sizes):
    """:func:`grouped_experts` behind its sort: ``(out [T, D], chunks run)``.
    One ``custom_vjp``, because a loop whose trip count is read on the device
    has no reverse-mode rule: the backward pass is the same loop over the
    same chunks."""
    return _dispatch_fwd_loop(act, rows, False, xt, gates, w_first, w_down,
                              b_in, b_down, order, slot, group_sizes)[0]


def _dispatch_fwd(act, rows, xt, gates, w_first, w_down, b_in, b_down, order,
                  slot, group_sizes):
    out, (hidden,) = _dispatch_fwd_loop(
        act, rows, True, xt, gates, w_first, w_down, b_in, b_down, order,
        slot, group_sizes)
    # a rematerialised block keeps the first product's rows, written in the
    # chunks that ran and read in no other
    hidden = checkpoint_name(hidden, "expert_hidden")
    return out, (xt, gates, w_first, w_down, b_in, b_down, order, slot,
                 group_sizes, hidden)


def _dispatch_bwd(act, rows, res, cotangents):
    (xt, gates, w_first, w_down, b_in, b_down, order, slot, group_sizes,
     hidden) = res
    g = cotangents[0]
    k = gates.shape[1]
    order, chunks = _chunks(order, group_sizes, rows)
    flat_gates = gates.reshape(-1, 1)
    held = group_sizes.shape[0]

    def product_vjp(x, w, sizes, ct):
        # the two products of the backward pass for one of the forward's
        d_x, = jax.linear_transpose(
            lambda x_: lax.ragged_dot(x_, w, sizes), x)(ct)
        d_w, = jax.linear_transpose(
            lambda w_: lax.ragged_dot(x, w_, sizes), w)(ct)
        return d_x, d_w.astype(jnp.float32)

    def by_expert(rows_, expert):
        return jnp.zeros((held, rows_.shape[1]), jnp.float32).at[expert].add(
            rows_.astype(jnp.float32))

    def body(i, carry):
        d_rows, d_gate, d_first, d_down, d_b_in, d_b_down = carry
        picked, sizes, mask, expert = _chunk(i, rows, order, group_sizes)
        token = picked // k
        x = jnp.where(mask, xt[token], 0)
        g_y = jnp.where(mask, g[token], 0)
        h = lax.dynamic_slice_in_dim(hidden, i * rows, rows)
        if b_in is not None:
            h = h + b_in[expert]
        a, act_vjp = jax.vjp(lambda h_: expert_act(h_, act), h)
        gate = jnp.where(mask, flat_gates[picked], 0)
        g_gated, d_w = product_vjp(a * gate, w_down, sizes, g_y)
        g_gated = jnp.where(mask, g_gated, 0)
        d_down = d_down + d_w
        g_gate = jnp.sum((g_gated * a).astype(jnp.float32), axis=1)
        if b_down is not None:
            g_gate = g_gate + jnp.sum(
                (g_y * b_down[expert]).astype(jnp.float32), axis=1)
            d_b_down = d_b_down + by_expert(g_y * gate, expert)
        g_h, = act_vjp(g_gated * gate)
        if b_in is not None:
            d_b_in = d_b_in + by_expert(g_h, expert)
        g_x, d_w = product_vjp(x, w_first, sizes, g_h)
        d_first = d_first + d_w
        return (lax.dynamic_update_slice_in_dim(
                    d_rows, jnp.where(mask, g_x, 0), i * rows, 0),
                lax.dynamic_update_slice_in_dim(
                    d_gate, g_gate.astype(d_gate.dtype), i * rows, 0),
                d_first, d_down, d_b_in, d_b_down)

    zeros = lambda like: (None if like is None           # noqa: E731
                          else jnp.zeros(like.shape, jnp.float32))
    d_rows, d_gate, *d_leaves = lax.fori_loop(0, chunks, body, (
        jnp.zeros((order.shape[0], xt.shape[1]), xt.dtype),
        jnp.zeros(order.shape, gates.dtype),
        zeros(w_first), zeros(w_down), zeros(b_in), zeros(b_down)))
    # a token's cotangent is the sum of its k rows', a gate's its own row's
    # (read through ``slot``, a row no chunk wrote zero)
    d_first, d_down, d_b_in, d_b_down = (
        None if d is None else d.astype(like.dtype)
        for d, like in zip(d_leaves, (w_first, w_down, b_in, b_down)))
    return (_to_tokens(d_rows, order, slot, chunks, rows, k),
            d_gate[slot.reshape(-1, k)], d_first, d_down, d_b_in, d_b_down,
            None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def grouped_experts(xt, local, weights, w_in, w_down, act: str, dtype,
                    b_in=None, b_down=None, rows: int | None = None):
    """The experts' part of a routed layer without dense dispatch tensors:
    ``xt [T, D]``, ``local [T, k]`` (:func:`sort_by_expert`'s), ``weights
    [T, k]``; ``w_in`` one ``[E, D, F]`` stack (``act="gelu"``, ``"relu2"``: the
    fused first product in its one-matrix form) or the gate's and the up
    projection's (``"swiglu"``), ``w_down [E, F, D]``, optional
    biases ``[E, F]`` / ``[E, D]``. The assignments to run are sorted by
    expert and go through grouped matrix products (``lax.ragged_dot``).
    Returns ``(out [T, D], group_sizes [E], share)``.

    The buffer has ``k * T`` rows — the worst case, every choice of every
    token run here — so no routing can drop a token for want of room, but the
    dispatch follows the rows that fell here: everything between the gather
    in and the gather back runs in chunks of ``rows`` rows (static; the
    callers take it from their shapes, :func:`chunk_rows`; all ``k * T``
    where none is given) up to the assigned count, ``ceil(sum(group_sizes) /
    rows)`` times, a trip count read on the device. The worst case runs
    every chunk. ``share`` is the rows that ran over ``k * T`` (1.0: the
    whole buffer). The way back, and a token's cotangent on the way in, are
    :func:`_to_tokens`': a gather through ``slot`` over all ``k * T`` slots,
    a slot that was not run reading a zero row, or, while under half the
    buffer ran, a scatter-add of the chunks that ran.

    The grouped products are the one part of a step whose time follows the
    routing (v5e, 16 experts 2048 x 768, 16,384 tokens, forward and backward:
    3.7 ms for every 16,384 assignments), so none is made twice — a
    rematerialised block keeps the first product's output
    (``expert_hidden``) and the sort its rows lie in (``expert_sort``), and a
    row's gate goes in BEFORE the down product, so that the backward pass
    asks for no product's output — and the gate's and the up projection's go
    as one product of twice the width, which runs a sixth faster a row than
    the two. ``b_in`` is as wide as that product. An ungated expert's hidden
    width is padded with zero columns to the products' tile
    (:func:`grouped_pad`): ``act(0) = 0`` meets zero rows of ``w_down``, so
    nothing changes but the products' rate."""
    order, slot, group_sizes = sort_by_expert(local, w_down.shape[0])
    rows = min(rows or order.shape[0], order.shape[0])
    pad = grouped_pad(w_down.shape[1]) if len(w_in) == 1 else 0
    w_first = jnp.concatenate([w.astype(dtype) for w in w_in], axis=-1)
    w_down = w_down.astype(dtype)
    if b_in is not None:
        b_in = b_in.astype(dtype)
    if pad:
        w_first = jnp.pad(w_first, ((0, 0), (0, 0), (0, pad)))
        w_down = jnp.pad(w_down, ((0, 0), (0, pad), (0, 0)))
        if b_in is not None:
            b_in = jnp.pad(b_in, ((0, 0), (0, pad)))
    out, chunks = _dispatch(
        act, rows, xt.astype(dtype), weights.astype(dtype), w_first, w_down,
        b_in, None if b_down is None else b_down.astype(dtype), order, slot,
        group_sizes)
    return out, group_sizes, chunks * rows / order.shape[0]


class MoEMlp(nn.Module):
    """Drop-in MoE replacement for a transformer's dense MLP block.

    ``expert_axis=None``: every expert computed locally, the kept assignments
    through :func:`grouped_experts`. ``expert_axis='data'`` (inside
    shard_map): expert parallelism — experts partitioned across the axis,
    per-expert token blocks ``[E, C, D]`` exchanged via ``lax.all_to_all``.
    The axis size must divide ``num_experts``.
    """

    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    expert_axis: str | None = None
    no_drop: bool = False    # inference/decode: capacity = T, never drop — a
                             # generated continuation must not depend on which
                             # other batch entries route to the same expert
    router: str = "top1"     # "top1" (Switch) or "top2" (GShard, renormalized
                             # pair gates; cf is per-choice, so effective
                             # capacity doubles relative to top1 at equal cf)

    @nn.compact
    def __call__(self, x):
        _, k = router_fn(self.router)
        b, s, d = x.shape
        t = b * s
        e = self.num_experts
        if k > e:
            raise ValueError(f"{self.router} routing needs at least {k} "
                             f"experts, got {e}")
        xt = x.reshape(t, d)

        gate_logits = nn.Dense(e, dtype=jnp.float32, name="gate")(
            xt.astype(jnp.float32))
        capacity = (t if self.no_drop
                    else expert_capacity(self.capacity_factor, k, t, e))
        weights, experts, keep, pos, aux, _ = capacity_routing(
            gate_logits, capacity, k)
        self.sow("intermediates", "moe_aux_loss", aux)
        # Raw router scores for offline capacity sweeps
        # (tools/moe_capacity_sweep.py); unused sows are dead-code-eliminated
        # by XLA in training steps.
        self.sow("intermediates", "gate_logits", gate_logits)

        # Stacked expert weights, identical param layout with and without EP.
        k_init = nn.initializers.lecun_normal()
        w1 = self.param("w1", k_init, (e, d, self.mlp_dim), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (e, self.mlp_dim),
                        jnp.float32)
        w2 = self.param("w2", k_init, (e, self.mlp_dim, d), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (e, d), jnp.float32)

        if self.expert_axis is None:
            out, loads, _ = grouped_experts(
                xt, jnp.where(keep, experts, -1), weights, [w1], w2, "gelu",
                self.dtype, b1, b2)
            self.sow("intermediates", "moe_counts",
                     load_counts(loads, k * t, t))
            return out.reshape(b, s, d)

        n = axis_size(self.expert_axis)
        if e % n:
            raise ValueError(f"num_experts {e} not divisible by "
                             f"{self.expert_axis!r} axis size {n}")
        e_local = e // n
        me = lax.axis_index(self.expert_axis)
        dispatch, combine = dense_dispatch(weights, experts, keep, pos, e,
                                           capacity, self.dtype)
        self.sow("intermediates", "moe_counts",
                 load_counts(jnp.sum(dispatch, axis=(0, 2)), k * t, t))
        # [T, E, C] x [T, D] -> per-expert token blocks [E, C, D]
        expert_in = jnp.einsum("tec,td->ecd", dispatch, xt.astype(self.dtype))
        # Ship each expert's token block to its owner rank: regroup the
        # expert dim by owner, all_to_all over the owner dim. Result on rank
        # r: [n_src, E_local, C, D] — r's experts' tokens from every source
        # rank.
        received = lax.all_to_all(
            expert_in.reshape(n, e_local, capacity, d), self.expert_axis,
            split_axis=0, concat_axis=0, tiled=False)
        sl = lambda p: lax.dynamic_slice_in_dim(  # noqa: E731
            p, me * e_local, e_local, axis=0).astype(self.dtype)
        h = jnp.einsum("necd,edh->nech", received, sl(w1))
        h = nn.gelu(h + sl(b1)[:, None, :])
        out_blocks = (jnp.einsum("nech,ehd->necd", h, sl(w2))
                      + sl(b2)[:, None, :])
        # Inverse exchange: results back to the tokens' source ranks.
        returned = lax.all_to_all(out_blocks, self.expert_axis,
                                  split_axis=0, concat_axis=0, tiled=False)
        out = jnp.einsum("tec,ecd->td", combine,
                         returned.reshape(e, capacity, d))
        return out.reshape(b, s, d)


class RoutedExperts(nn.Module):
    """Top-``k`` of ``router_width`` experts, none dropped, on the share of
    the experts this chip holds (the ``model-configs`` guide's section 4 cut,
    and what expert parallelism asks of a layer anyway).

    The router scores every expert of the deployment. The assignments that
    fall on the ``num_held`` experts ``[offset, offset + num_held)`` go
    through :func:`grouped_experts`. What the experts held elsewhere would
    have added is left out: nothing here stands in for the other chips or
    their exchange. Experts are gated (``swiglu``: ``down(silu(gate x) * up
    x)``), plain (``gelu``) or squared (``relu2``: ``down(relu(up x)^2)``),
    without biases.

    ``score="sigmoid"`` (:func:`route_sigmoid`) scores each expert alone;
    with ``bias_rate > 0`` the choice also reads a correction bias an expert,
    a variable of the ``buffers`` collection: no gradient, no optimizer
    state, moved after each step by :func:`step_router_bias` from the loads
    sown here as ``router_load``. ``shared_dim > 0`` adds an expert of that
    width that every token takes; every chip of a deployment computes it
    whole, so across shares it counts once.

    Sows ``moe_counts`` (:func:`load_counts`: assignments to held experts a
    token, the largest load of a held expert over their mean load, and
    assignments to a held expert that found no row of the buffer: zero by
    construction), the counter ``router_bias_range`` where there is a bias,
    and ``expert_choice``, for a reader that asks.
    """

    num_held: int
    mlp_dim: int
    k: int
    router_width: int = 0
    offset: int = 0
    normalise: bool = True
    act: str = "swiglu"
    dtype: Any = jnp.bfloat16
    score: str = "softmax"
    scale: float = 1.0
    bias_rate: float = 0.0
    shared_dim: int = 0

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        t = b * s
        width = self.router_width or self.num_held
        if not 0 < self.k <= width:
            raise ValueError(f"experts_per_token {self.k} must lie in "
                             f"1..{width}")
        if self.offset < 0 or self.offset + self.num_held > width:
            raise ValueError(f"held experts [{self.offset}, "
                             f"{self.offset + self.num_held}) lie outside "
                             f"the router's {width}")
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_score {self.score!r}; use "
                             f"'softmax' or 'sigmoid'")
        if self.bias_rate and self.score != "sigmoid":
            raise ValueError("the correction bias steers a sigmoid router's "
                             "choice; router_score is 'softmax'")
        xt = x.reshape(t, d)
        with jax.named_scope("router"):
            # float32 all the way: which expert is 8th decides a token's path
            gate_logits = nn.Dense(
                width, use_bias=False, dtype=jnp.float32,
                precision=lax.Precision.HIGHEST, name="gate")(
                    xt.astype(jnp.float32))
            if self.score == "sigmoid":
                bias = None
                if self.bias_rate:
                    bias = self.variable("buffers", "router_bias", jnp.zeros,
                                         (width,), jnp.float32).value
                    self.sow("intermediates", "counters", {
                        "router_bias_range": jnp.max(bias) - jnp.min(bias)})
                weights, experts, _ = route_sigmoid(
                    gate_logits, self.k, self.normalise, bias, self.scale)
            else:
                weights, experts, _ = route_topk(gate_logits, self.k,
                                                 self.normalise)
                weights = weights * self.scale if self.scale != 1.0 else weights
            if self.bias_rate:
                self.sow("intermediates", "router_load", jnp.sum(
                    experts[..., None] == jnp.arange(width), axis=(0, 1),
                    dtype=jnp.float32))
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        e, f = self.num_held, self.mlp_dim
        names = (("w_gate", "w_up") if self.act == "swiglu" else ("w_up",))
        w_in = [self.param(n, init, (e, d, f), jnp.float32) for n in names]
        w_down = self.param("w_down", init, (e, f, d), jnp.float32)
        with jax.named_scope("experts"):
            out, loads, share = grouped_experts(
                xt, experts - self.offset, weights, w_in, w_down, self.act,
                self.dtype, rows=chunk_rows(self.k * t, e, width))
        self.sow("intermediates", "counters", {"moe_rows_run_share": share})
        if self.shared_dim:
            if self.act not in ("relu2", "swiglu"):
                raise NotImplementedError("the shared expert is written for "
                                          "mlp='relu2' (up, down) and "
                                          "'swiglu' (gate, up, down)")
            with jax.named_scope("shared_expert"):
                dense = lambda width, name: nn.Dense(     # noqa: E731
                    width, use_bias=False, dtype=self.dtype, name=name)
                hidden = dense(self.shared_dim, "shared_up")(xt)
                if self.act == "swiglu":
                    hidden = nn.silu(dense(self.shared_dim,
                                           "shared_gate")(xt)) * hidden
                else:
                    hidden = jnp.square(nn.relu(hidden))
                out = out + dense(d, "shared_down")(hidden)
        self.sow("intermediates", "expert_choice", experts)
        here = (experts >= self.offset) & (experts < self.offset + e)
        self.sow("intermediates", "moe_counts",
                 load_counts(loads, jnp.sum(here), t))
        return out.reshape(b, s, d)
