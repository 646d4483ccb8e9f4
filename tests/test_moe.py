"""MoE / expert parallelism: routing invariants, EP==dense equivalence, training.

The EP equivalence tests use a capacity factor large enough that no token
drops; routing and combine weights are then identical between the dense path
and the all_to_all expert-parallel path, so outputs must match to float
tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ddw_tpu.models.lm import TransformerLM
from ddw_tpu.models.moe import MoEMlp, top1_routing
from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS
from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step

VOCAB = 32


def moe_lm(expert_axis=None, num_experts=4, cf=8.0):
    return TransformerLM(vocab_size=VOCAB, max_len=64, hidden=32, depth=2,
                         num_heads=2, mlp_dim=64, dropout=0.0,
                         dtype=jnp.float32, num_experts=num_experts,
                         expert_axis=expert_axis, capacity_factor=cf)


def test_top1_routing_invariants():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(64, 4).astype(np.float32))
    dispatch, combine, aux, stats = top1_routing(logits, capacity=64)
    # no drops at full capacity: every token dispatched exactly once
    np.testing.assert_allclose(np.asarray(dispatch.sum((1, 2))), 1.0)
    # combine = gate prob of the chosen expert
    probs = jax.nn.softmax(np.asarray(logits), -1)
    np.testing.assert_allclose(np.asarray(combine.sum((1, 2))),
                               probs.max(-1), rtol=1e-6)
    # each (expert, slot) holds at most one token
    assert float(np.asarray(dispatch.sum(0)).max()) <= 1.0 + 1e-6
    assert np.isfinite(float(aux)) and float(aux) >= 1.0 - 1e-6
    assert float(stats["drop_rate"]) == 0.0

    # tight capacity: overflow tokens get empty dispatch rows, never doubled
    dispatch2, _, _, stats2 = top1_routing(logits, capacity=2)
    per_tok = np.asarray(dispatch2.sum((1, 2)))
    assert set(np.round(per_tok, 6)) <= {0.0, 1.0}
    assert float(np.asarray(dispatch2.sum((0, 2))).max()) <= 2.0 + 1e-6
    # telemetry agrees with the dispatch tensor
    np.testing.assert_allclose(float(stats2["drop_rate"]),
                               1.0 - per_tok.mean(), rtol=1e-6)


def test_no_drop_at_capacity_one_with_balanced_routing():
    """The Switch contract pinned (VERDICT r2 item 7): with perfectly balanced
    routing, capacity factor 1.0 (C = T/E exactly) drops nothing; entropy
    telemetry reads 1.0. A fully collapsed router at cf=1 drops 1 - C/T."""
    t, e = 64, 4
    balanced = jax.nn.one_hot(jnp.arange(t) % e, e) * 10.0  # T/E tokens each
    cap = t // e  # ceil(1.0 * T / E)
    dispatch, _, _, stats = top1_routing(balanced, capacity=cap)
    np.testing.assert_allclose(np.asarray(dispatch.sum((1, 2))), 1.0)
    assert float(stats["drop_rate"]) == 0.0
    np.testing.assert_allclose(float(stats["balance_entropy"]), 1.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(stats["expert_frac"]), 1.0 / e)

    collapsed = jnp.zeros((t, e)).at[:, 0].set(10.0)  # everyone -> expert 0
    _, _, _, s2 = top1_routing(collapsed, capacity=cap)
    np.testing.assert_allclose(float(s2["drop_rate"]), 1.0 - cap / t, rtol=1e-6)
    assert float(s2["balance_entropy"]) < 0.01


def test_moe_layer_ep_matches_dense():
    """MoEMlp under shard_map(expert axis over 4 devices) == dense MoEMlp,
    same params, tokens sharded over the same axis."""
    n = 4
    mesh = make_mesh(MeshSpec(((DATA_AXIS, n),)), devices=jax.devices()[:n])
    dense = MoEMlp(num_experts=4, mlp_dim=32, capacity_factor=16.0,
                   dtype=jnp.float32, expert_axis=None)
    ep = MoEMlp(num_experts=4, mlp_dim=32, capacity_factor=16.0,
                dtype=jnp.float32, expert_axis=DATA_AXIS)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 6, 16).astype(np.float32))
    params = dense.init(jax.random.PRNGKey(0), x)["params"]

    ref = dense.apply({"params": params}, x)
    ep_fwd = jax.jit(shard_map(
        lambda p, x: ep.apply({"params": p}, x),
        mesh=mesh, in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS), check_vma=False))
    out = ep_fwd(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_moe_lm_ep_train_step_matches_dense():
    """One DPxEP train step (experts over the data axis) == the same step with
    dense (all-local) experts: same params, grads, metrics."""
    n = 4
    mesh = make_mesh(MeshSpec(((DATA_AXIS, n),)), devices=jax.devices()[:n])
    tx = optax.sgd(1e-1)
    rng = np.random.RandomState(2)
    tokens = rng.randint(0, VOCAB, size=(8, 17)).astype(np.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    results = {}
    for name, axis in (("dense", None), ("ep", DATA_AXIS)):
        model = moe_lm(expert_axis=axis)
        state = init_lm_state(model, tx, jax.random.PRNGKey(3))
        step = make_lm_train_step(model, tx, mesh, DATA_AXIS, seq_axis=None,
                                  donate=False)
        new, m = step(state, inputs, targets, jax.random.PRNGKey(4))
        results[name] = (new, m)

    m_d, m_e = results["dense"][1], results["ep"][1]
    # Routing is per-shard under EP (each rank's token block routes
    # independently) but with no drops at cf=8 the expert computation is
    # identical; CE/accuracy must match, aux differs only by shard averaging.
    assert abs(float(m_d["loss"]) - float(m_e["loss"])) < 1e-5
    assert abs(float(m_d["accuracy"]) - float(m_e["accuracy"])) < 1e-6
    for a, b in zip(jax.tree.leaves(results["dense"][0].params),
                    jax.tree.leaves(results["ep"][0].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # tier-1 budget (PR 16): MoE train math keeps its tier-1
#                    pin in test_moe_lm_ep_train_step_matches_dense (+ the
#                    top2 EP-vs-dense arm); this learning soak rides tier-2
#                    with test_top2_lm_trains_and_validates
def test_moe_lm_learns():
    """A few MoE LM steps memorize a repeating pattern; aux loss stays near 1
    (balanced) rather than collapsing to one expert."""
    n = 4
    mesh = make_mesh(MeshSpec(((DATA_AXIS, n),)), devices=jax.devices()[:n])
    model = moe_lm(expert_axis=DATA_AXIS, cf=2.0)
    tx = optax.adam(5e-3)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0))
    step = make_lm_train_step(model, tx, mesh, DATA_AXIS, seq_axis=None)

    seq = np.tile(np.arange(16, dtype=np.int32) % VOCAB, (8, 1))
    inputs, targets = seq[:, :-1][:, :12], seq[:, 1:][:, :12]
    first = None
    for i in range(30):
        state, metrics = step(state, inputs, targets, jax.random.PRNGKey(i))
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first / 3
    assert float(metrics["aux_loss"]) < 2.5  # not collapsed (1.0 = perfect)


def test_moe_expert_axis_must_divide():
    n = 4
    mesh = make_mesh(MeshSpec(((DATA_AXIS, n),)), devices=jax.devices()[:n])
    ep = MoEMlp(num_experts=6, mlp_dim=16, dtype=jnp.float32,
                expert_axis=DATA_AXIS)
    x = jnp.zeros((4, 2, 8), jnp.float32)
    params = MoEMlp(num_experts=6, mlp_dim=16, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), x)["params"]
    fwd = jax.jit(shard_map(
        lambda p, x: ep.apply({"params": p}, x),
        mesh=mesh, in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS), check_vma=False))
    with pytest.raises(ValueError, match="not divisible"):
        fwd(params, x)


def test_moe_step_rejects_foreign_expert_axis():
    mesh = make_mesh(MeshSpec(((DATA_AXIS, 2),)), devices=jax.devices()[:2])
    model = moe_lm(expert_axis="nonexistent")
    with pytest.raises(ValueError, match="expert_axis"):
        make_lm_train_step(model, optax.adam(1e-3), mesh, DATA_AXIS,
                           seq_axis=None)


@pytest.mark.slow  # ~9s; tier-1 reps: test_moe_lm_ep_train_step_matches_dense
# (moe train math)
# + test_lm.py::test_decode_path_matches_full_forward (decode identity)
def test_moe_decode_path_matches_full_forward():
    """KV-cached decode of an MoE LM (dense experts, per-call routing) ==
    full-sequence forward at no-drop capacity — prefill and per-token both."""
    from ddw_tpu.models.lm import init_cache

    model = TransformerLM(vocab_size=VOCAB, max_len=64, hidden=32, depth=2,
                          num_heads=2, mlp_dim=64, dropout=0.0,
                          dtype=jnp.float32, num_experts=4,
                          capacity_factor=8.0)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, VOCAB, size=(2, 12)).astype(np.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]
    full = model.apply({"params": params}, tokens)

    dm = model.clone(decode=True, seq_axis=None)
    cache = init_cache(dm, 2)
    prefill, vars_ = dm.apply({"params": params, "cache": cache}, tokens,
                              mutable=["cache"])
    np.testing.assert_allclose(np.asarray(prefill), np.asarray(full),
                               rtol=1e-5, atol=1e-5)

    cache = init_cache(dm, 2)
    outs = []
    for t in range(12):
        lg, vars_ = dm.apply({"params": params, "cache": cache},
                             tokens[:, t:t + 1], mutable=["cache"])
        cache = vars_["cache"]
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), rtol=1e-5, atol=1e-5)


def test_top2_routing_invariants():
    """Ample capacity: every token reaches exactly its two top experts with
    renormalized gates summing to 1; capacity pressure drops second choices
    after first choices claimed their slots."""
    from ddw_tpu.models.moe import top2_routing

    rng = np.random.RandomState(0)
    t, e, cap = 12, 4, 12
    logits = jnp.asarray(rng.randn(t, e).astype(np.float32) * 2)
    dispatch, combine, aux, stats = top2_routing(logits, cap)
    assert dispatch.shape == combine.shape == (t, e, cap)
    # two dispatch slots per token, combine mass 1 per token
    np.testing.assert_allclose(np.asarray(dispatch.sum((1, 2))),
                               np.full(t, 2.0), atol=1e-6)
    np.testing.assert_allclose(np.asarray(combine.sum((1, 2))),
                               np.ones(t), atol=1e-6)
    assert float(stats["drop_rate"]) == 0.0
    # the two chosen experts match top_k of the softmax
    probs = jax.nn.softmax(logits, -1)
    top2 = np.asarray(jax.lax.top_k(probs, 2)[1])
    got = np.asarray(dispatch.sum(-1))  # [T, E] 0/1
    for i in range(t):
        assert set(np.nonzero(got[i])[0]) == set(top2[i])
    # no expert queue exceeds its claimed count; per-slot uniqueness
    assert np.all(np.asarray(dispatch.sum((0, 2))) <= cap + 1e-6)
    assert np.all(np.asarray(dispatch.sum(0)) <= 1.0 + 1e-6)

    # capacity 1: each expert serves one slot; first choices outrank second
    d1, c1, _, s1 = top2_routing(logits, 1)
    assert float(s1["drop_rate"]) > 0
    assert np.all(np.asarray(d1.sum((0, 2))) <= 1.0 + 1e-6)


def test_top2_moe_lm_ep_matches_dense():
    """The EP all_to_all path is router-agnostic: top2 EP == top2 dense."""
    n = 4
    mesh = make_mesh(MeshSpec(((DATA_AXIS, n),)), devices=jax.devices()[:n])
    dense = MoEMlp(num_experts=4, mlp_dim=32, capacity_factor=16.0,
                   dtype=jnp.float32, expert_axis=None, router="top2")
    ep = MoEMlp(num_experts=4, mlp_dim=32, capacity_factor=16.0,
                dtype=jnp.float32, expert_axis=DATA_AXIS, router="top2")
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(8, 6, 16).astype(np.float32))
    params = dense.init(jax.random.PRNGKey(0), x)["params"]
    ref = dense.apply({"params": params}, x)
    ep_fwd = jax.jit(shard_map(
        lambda p, x: ep.apply({"params": p}, x),
        mesh=mesh, in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS), check_vma=False))
    np.testing.assert_allclose(np.asarray(ep_fwd(params, x)),
                               np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # ~8s; top2 keeps tier-1 reps in routing invariants +
#                    EP-matches-dense, the MoE train-math pin in
#                    test_moe_lm_ep_train_step_matches_dense
def test_top2_lm_trains_and_validates():
    model = TransformerLM(vocab_size=VOCAB, max_len=64, hidden=32, depth=2,
                          num_heads=2, mlp_dim=64, dropout=0.0,
                          dtype=jnp.float32, num_experts=4,
                          capacity_factor=2.0, moe_router="top2")
    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)))
    state = init_lm_state(model, optax.adam(3e-3), jax.random.PRNGKey(0))
    step = make_lm_train_step(model, optax.adam(3e-3), mesh, DATA_AXIS,
                              seq_axis=None, donate=False)
    rng = np.random.RandomState(3)
    start = rng.randint(0, VOCAB, (8, 1))
    toks = jnp.asarray((start + np.arange(17)) % VOCAB)
    first = last = None
    for i in range(40):
        state, m = step(state, toks[:, :-1], toks[:, 1:], jax.random.PRNGKey(i))
        first = first or float(m["loss"])
        last = float(m["loss"])
    assert last < 0.7 * first, (first, last)

    with pytest.raises(ValueError, match="unknown router"):
        MoEMlp(num_experts=4, mlp_dim=8, router="top3").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, 8)))
    with pytest.raises(ValueError, match="at least 2 experts"):
        MoEMlp(num_experts=1, mlp_dim=8, router="top2").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, 8)))


@pytest.mark.parametrize("router,cf", [("top1", 16.0), ("top1", 0.5),
                                       ("top2", 16.0), ("top2", 0.5)])
def test_local_experts_sorted_dispatch_matches_the_dense_tensors(router, cf):
    """``MoEMlp`` runs local experts through the sorted, grouped dispatch;
    the ``[T, E, C]`` dispatch and combine tensors (what the expert-parallel
    exchange ships) give the same layer, dropped assignments and all."""
    from ddw_tpu.models.moe import expert_capacity, router_fn

    layer = MoEMlp(num_experts=4, mlp_dim=32, capacity_factor=cf,
                   dtype=jnp.float32, router=router)
    x = jnp.asarray(np.random.RandomState(5).randn(4, 6, 16), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    p = jax.tree.map(lambda a: a + 0.1, variables["params"])   # biases too
    out, mods = layer.apply({"params": p}, x, mutable=["intermediates"])

    route, k = router_fn(router)
    xt = x.reshape(-1, 16)
    logits = xt @ p["gate"]["kernel"] + p["gate"]["bias"]
    cap = expert_capacity(cf, k, xt.shape[0], 4)
    dispatch, combine, _, stats = route(logits, cap)
    blocks = jnp.einsum("tec,td->ecd", dispatch, xt)
    h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", blocks, p["w1"])
                    + p["b1"][:, None])
    y = jnp.einsum("ech,ehd->ecd", h, p["w2"]) + p["b2"][:, None]
    want = jnp.einsum("tec,ecd->td", combine, y)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 16),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    counts = mods["intermediates"]["moe_counts"][0]
    np.testing.assert_allclose(float(counts["dropped"]),
                               float(stats["drop_rate"]) * k * xt.shape[0])
    assert (float(counts["dropped"]) > 0) == (cf < 1)


@pytest.mark.parametrize("router", ["top1", "top2"])
def test_local_experts_sorted_dispatch_gradients_match_the_dense_tensors(router):
    """The same pair in the backward pass: every leaf's gradient and the
    input's, through the sorted dispatch's gathers (rows, gates and their
    inverses) and through the dense tensors, with assignments dropped."""
    from ddw_tpu.models.moe import expert_capacity, router_fn

    layer = MoEMlp(num_experts=4, mlp_dim=32, capacity_factor=0.75,
                   dtype=jnp.float32, router=router)
    x = jnp.asarray(np.random.RandomState(6).randn(4, 6, 16), jnp.float32)
    p = jax.tree.map(lambda a: a + 0.1,
                     layer.init(jax.random.PRNGKey(0), x)["params"])
    probe = jnp.asarray(np.random.RandomState(7).randn(24, 16), jnp.float32)
    route, k = router_fn(router)

    def sorted_form(p, x):
        return jnp.sum(layer.apply({"params": p}, x).reshape(-1, 16) * probe)

    def dense_form(p, x):
        xt = x.reshape(-1, 16)
        logits = xt @ p["gate"]["kernel"] + p["gate"]["bias"]
        dispatch, combine, _, _ = route(
            logits, expert_capacity(0.75, k, xt.shape[0], 4))
        blocks = jnp.einsum("tec,td->ecd", dispatch, xt)
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", blocks, p["w1"])
                        + p["b1"][:, None])
        y = jnp.einsum("ech,ehd->ecd", h, p["w2"]) + p["b2"][:, None]
        return jnp.sum(jnp.einsum("tec,ecd->td", combine, y) * probe)

    got = jax.grad(sorted_form, argnums=(0, 1))(p, x)
    want = jax.grad(dense_form, argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)


def one_pass_experts(xt, local, weights, w_in, w_down, act, b_in=None,
                     b_down=None):
    """The dispatch as it ran before it was chunked, the plain reference:
    once over all ``k * T`` rows of the sorted order, rows past the assigned
    count selected to zero on both sides of the products; no padded width,
    plain gathers, autodiff's own backward pass."""
    held, k = w_down.shape[0], local.shape[1]
    flat = local.reshape(-1)
    key = jnp.where((flat >= 0) & (flat < held), flat, held)
    order = jnp.argsort(key, stable=True)
    slot = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0])).reshape(local.shape)
    group_sizes = jnp.sum(key[:, None] == jnp.arange(held)[None], axis=0,
                          dtype=jnp.int32)
    mask = (jnp.arange(order.shape[0]) < jnp.sum(group_sizes))[:, None]
    expert = jnp.minimum(key[order], held - 1)
    h = jax.lax.ragged_dot(jnp.where(mask, xt[order // k], 0),
                           jnp.concatenate(w_in, axis=-1), group_sizes)
    if b_in is not None:
        h = h + b_in[expert]
    if act == "swiglu":
        h = jax.nn.silu(h[:, :h.shape[1] // 2]) * h[:, h.shape[1] // 2:]
    else:
        h = jnp.square(jax.nn.relu(h)) if act == "relu2" else jax.nn.gelu(h)
    gate = jnp.where(mask, weights.reshape(-1, 1)[order], 0)
    y = jax.lax.ragged_dot(jnp.where(mask, h * gate, 0), w_down, group_sizes)
    if b_down is not None:
        y = y + gate * b_down[expert]
    return jnp.sum(jnp.where(mask, y, 0)[slot], axis=1)


CHUNK, CHOICES, TOKENS = 8, 2, 16           # R, k, T: a buffer of four chunks


# the assigned count: nothing, one row, around one chunk's edge, a third
# chunk begun, and every choice of every token held here (the worst case)
@pytest.mark.parametrize("count", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1,
                                   2 * CHUNK + 3, CHOICES * TOKENS])
@pytest.mark.parametrize("act,width,biases", [("swiglu", 24, False),
                                              ("relu2", 960, False),
                                              ("gelu", 24, True)])
def test_the_chunked_dispatch_is_the_one_pass_form(act, width, biases, count):
    """``grouped_experts`` runs its buffer in chunks of ``rows`` up to the
    assigned count and no further (``moe_rows_run_share``: the chunks that
    ran over ``k * T``), and gives what one pass over all ``k * T`` rows
    gives: the output and every gradient, for gated experts, for squared ones
    whose width is padded to the products' tile (960 runs as 1,024) and for
    ``MoEMlp``'s with biases. The worst case runs every chunk and drops
    nothing."""
    from ddw_tpu.models.moe import grouped_experts

    d, e, rows = 16, 3, CHOICES * TOKENS
    rng = np.random.RandomState(count)
    local = np.full(rows, -1)
    local[rng.permutation(rows)[:count]] = rng.randint(0, e, count)
    local[local < 0] = rng.choice([-1, e, e + 5], rows - count)  # elsewhere
    local = jnp.asarray(local.reshape(TOKENS, CHOICES))
    leaf = lambda *shape: jnp.asarray(0.3 * rng.randn(*shape),      # noqa: E731
                                      jnp.float32)
    args = {"xt": leaf(TOKENS, d), "weights": jnp.abs(leaf(TOKENS, CHOICES)),
            "w_in": [leaf(e, d, width) for _ in range(1 + (act == "swiglu"))],
            "w_down": leaf(e, width, d)}
    if biases:
        args.update(b_in=leaf(e, width), b_down=leaf(e, d))
    probe = leaf(TOKENS, d)

    def chunked(args):
        out, loads, share = grouped_experts(
            args["xt"], local, args["weights"], args["w_in"], args["w_down"],
            act, jnp.float32, args.get("b_in"), args.get("b_down"),
            rows=CHUNK)
        return jnp.sum(out * probe), (out, loads, share)

    def one_pass(args):
        out = one_pass_experts(
            args["xt"], local, args["weights"], args["w_in"], args["w_down"],
            act, args.get("b_in"), args.get("b_down"))
        return jnp.sum(out * probe), out

    (_, (out, loads, share)), got = jax.jit(
        jax.value_and_grad(chunked, has_aux=True))(args)
    (_, want_out), want = jax.value_and_grad(one_pass, has_aux=True)(args)
    assert int(jnp.sum(loads)) == count
    assert float(share) == -(-count // CHUNK) * CHUNK / rows
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-5)
    assert sorted(got) == sorted(want)
    for name in want:
        for g, w in zip(jax.tree.leaves(got[name]),
                        jax.tree.leaves(want[name])):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5,
                                       err_msg=name)


@pytest.mark.parametrize("rows,held,width,want", [
    (6 * 16384, 8, 128, 12288),      # a sixteenth of the experts: an eighth
    (8 * 16384, 16, 128, 32768),     # an eighth: a quarter of the buffer
    (2 * 24, 4, 4, 48),              # every expert held: one chunk, the buffer
    (3 * 1000, 2, 16, 1024),         # 750 rows, in whole tiles of 512
])
def test_a_chunk_is_twice_the_expected_load_in_whole_tiles(rows, held, width,
                                                           want):
    """The dispatch's chunk comes from shapes alone: twice what an
    indifferent router puts on the held experts, rounded up to the grouped
    products' tile, and never more than the buffer."""
    from ddw_tpu.models.moe import chunk_rows

    assert chunk_rows(rows, held, width) == want
