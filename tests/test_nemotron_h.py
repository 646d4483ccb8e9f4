"""One mixer a layer — the Mamba-2 state-space scan in its chunked form,
attention without positions, sigmoid-routed ``relu2`` experts beside a shared
one, a correction bias outside the optimizer — held against the plain float32
reference of the Nemotron-H decoder (``benchmark/reference/nemotron_h.py``,
whose scan is the token-by-token recurrence and which imports nothing of the
program), at a size the CPU holds: hidden 64, 8 Mamba heads of 8 in 2 groups
with state 16 and chunks of 8, 4/2 attention heads of 16, 16 experts top-3
with 4 held, 9 layers ``MEMEMEM*E`` at S = 32."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.families import lm_hybrid_ssm_moe_train as family
from benchmark.harness.manifest import ROOT, load_json
from benchmark.harness.step_probe import path_names, program_tree
from benchmark.harness.weights import seed_key, seeded_weights
from benchmark.reference import nemotron_h
from benchmark.reference.matmul import make_einsum
from ddw_tpu.models.lm import build_lm
from ddw_tpu.models.moe import RoutedExperts, route_sigmoid
from ddw_tpu.ops import ssd
from ddw_tpu.train.lm_step import (init_lm_state, layer_terms, lm_loss,
                                   make_lm_train_step)

PUBLISHED = load_json(ROOT + "/benchmark/configs/nemotron-3-nano-30b-a3b.json")
CONFIG = dict(PUBLISHED, **family.TINY["config"])
S = 32


@pytest.fixture(scope="module")
def both():
    """The model, the seeded reference weights, the same weights laid out as
    the program's tree, zeroed correction biases, a batch, and both sides'
    loss, counters and gradients on it (made once: each test reads them)."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
    weights = seeded_weights(seed_key(7), nemotron_h.weight_spec(CONFIG))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, S + 1), 0,
                                CONFIG["vocab_size"])
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), inputs))
    params = program_tree(shapes["params"], family.leaf_map(CONFIG), weights)
    buffers = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                           shapes["buffers"])

    def program(params):
        logits, mods = model.apply({"params": params, "buffers": buffers},
                                   inputs, train=True,
                                   mutable=["intermediates"])
        return lm_loss(logits, targets), (layer_terms(mods), logits)

    (loss, (terms, logits)), grads = jax.jit(
        jax.value_and_grad(program, has_aux=True))(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        nemotron_h.make_loss(CONFIG)))(weights, inputs, targets)
    return dict(model=model, weights=weights, params=params, buffers=buffers,
                inputs=inputs, targets=targets, loss=loss, terms=terms,
                logits=logits, grads=grads, ref_loss=ref_loss,
                ref_grads=ref_grads)


@pytest.fixture(scope="module")
def small():
    """Two layers, ``ME``, for what does not need all nine: the model, its
    configuration, seeded weights both ways, zeroed biases, a batch."""
    config = dict(CONFIG, hybrid_override_pattern="ME", num_hidden_layers=2)
    model = build_lm(family._lm_cfg(config, {"remat": "none"}))
    weights = seeded_weights(seed_key(5), nemotron_h.weight_spec(config))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, S + 1), 0,
                                config["vocab_size"])
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens[:, :-1]))
    params = program_tree(shapes["params"], family.leaf_map(config), weights)
    buffers = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                           shapes["buffers"])
    return dict(config=config, model=model, weights=weights, params=params,
                buffers=buffers, inputs=tokens[:, :-1], targets=tokens[:, 1:])


# -- the chunked scan against the recurrence ----------------------------------
def scan_inputs(s: int, h: int = 16, p: int = 4, g: int = 2, n: int = 8):
    """``dt`` in the published range (0.001 to 0.1), ``A`` in [-16, -1],
    eight heads a group."""
    k = jax.random.split(jax.random.PRNGKey(s), 5)
    x = jax.random.normal(k[0], (2, s, h, p))
    dt = jnp.exp(jax.random.uniform(k[1], (2, s, h), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    a = -jnp.exp(jax.random.uniform(k[2], (h,), maxval=jnp.log(16.0)))
    b = jax.random.normal(k[3], (2, s, g, n))
    c = jax.random.normal(k[4], (2, s, g, n))
    return x, dt, a, b, c


def recurrence(x, dt, a, b, c):
    return jax.vmap(nemotron_h.ssm_recurrence, (0, 0, None, 0, 0))(
        x, dt, a, b, c)


@pytest.mark.parametrize("s,chunk", [(48, 16), (70, 16)])
def test_the_chunked_scan_is_the_recurrence(s, chunk, monkeypatch):
    """Output and every input's gradient, at three chunks and at five (70
    does not divide: the last chunk is padded with tokens of dt = 0). Both
    sides are float32 on the CPU and differ by the order of their sums: 1e-5
    of the largest entry. With the state zeroed between chunks the same
    comparison is off by a tenth and more: the tolerance sees the carry. And
    decays that underflow float32 (dt A down to -200 a token) give zeros, not
    NaN or inf, forward and backward: every exponent is of a non-positive
    number, never a quotient of exponentials."""
    args = scan_inputs(s)
    probe = lambda f: lambda *t: jnp.sum(jnp.sin(f(*t)))    # noqa: E731
    want = jax.jit(recurrence)(*args)
    want_g = jax.jit(jax.grad(probe(recurrence), range(5)))(*args)

    def both_ways(*t):
        chunked = lambda *u: ssd.ssd_scan(*u, chunk)[0]     # noqa: E731
        return (*ssd.ssd_scan(*t, chunk),
                jax.grad(probe(chunked), range(5))(*t))

    def off(got, got_g):
        return max(float(jnp.max(jnp.abs(u - v)) / jnp.max(jnp.abs(v)))
                   for u, v in zip((got, *got_g), (want, *want_g)))

    run = jax.jit(both_ways)
    got, crossing, got_g = run(*args)
    assert off(got, got_g) < 1e-5
    assert crossing.shape == (2, -(-s // chunk), 16)
    assert 0.0 < float(crossing.min()) and float(crossing.max()) < 1.0
    x, dt, a, b, c = args
    assert all(bool(jnp.all(jnp.isfinite(t)))
               for t in jax.tree.leaves(run(x, dt * 2e3, a, b, c)))
    monkeypatch.setattr(ssd, "carry_states",
                        lambda local, decay: jnp.zeros_like(local))
    got, _, got_g = jax.jit(lambda *t: both_ways(*t))(*args)  # traced anew
    assert off(got, got_g) > 0.1


def test_the_causal_convolution_reads_the_token_and_the_three_before():
    x = jnp.arange(12.0).reshape(1, 6, 2)
    w = jnp.array([[1.0, 0], [10, 0], [100, 0], [1000, 1]])
    y = ssd.causal_conv1d(x, w, jnp.array([0.5, 0.0]))
    # channel 0: x[t-3] + 10 x[t-2] + 100 x[t-1] + 1000 x[t]; channel 1: x[t]
    np.testing.assert_allclose(y[0, :, 1], x[0, :, 1])
    np.testing.assert_allclose(
        y[0, :, 0], [0.5, 2000.5, 4200.5, 6420.5, 8642.5, 10864.5])


# -- the whole model against the reference ------------------------------------
def test_loss_logits_and_counters_agree_with_the_reference(both):
    """Float32 on both sides, so what is left is the order of the sums (the
    chunked scan against the recurrence, the sorted dispatch against a loop
    over experts): 1e-5 of the largest logit."""
    logits, _, _, carry, loads = nemotron_h.forward(
        both["weights"], both["inputs"], CONFIG)
    assert float(both["loss"]) == pytest.approx(float(both["ref_loss"]),
                                                rel=1e-6)
    np.testing.assert_allclose(both["logits"], logits,
                               atol=1e-5 * float(jnp.max(jnp.abs(logits))))
    terms = both["terms"]
    assert float(terms["ssm_chunk_carry"]) == pytest.approx(float(carry),
                                                            rel=1e-5)
    assert 0.05 < float(carry) < 0.95
    assert float(terms["moe_dropped"]) == 0.0
    assert float(terms["router_bias_range"]) == 0.0
    # every token chose 3 of the 16: the reference's loads say so too
    np.testing.assert_array_equal(loads.sum(axis=1), [2 * S * 3] * 4)
    held = float(jnp.mean(loads[:, :4].sum(axis=1))) / (2 * S)
    assert float(terms["moe_assignments_per_token"]) == pytest.approx(held)


def test_every_leafs_gradient_agrees_with_the_reference(both):
    """All 68 leaves of the nine layers, ``A_log``, ``dt_bias``, ``D`` and the
    convolution among them: within 1e-4 of the leaf's own largest entry (the
    smallest, ``A_log``'s, is 1e-6 in absolute terms) plus 1e-9."""
    mapping = family.leaf_map(CONFIG)
    flat = jax.tree_util.tree_flatten_with_path(both["grads"])[0]
    assert len(flat) == len(mapping) == 68
    for path, leaf in flat:
        want = both["ref_grads"][mapping[path_names(path)]]
        np.testing.assert_allclose(
            leaf.reshape(want.shape), want, rtol=0,
            atol=1e-4 * float(jnp.max(jnp.abs(want))) + 1e-9,
            err_msg=mapping[path_names(path)])


def test_dropping_the_carried_state_shows_in_the_logits(both, monkeypatch):
    """The planted fault of the benchmark's control, at the tiny size: with
    the state zeroed between chunks the logits move by a hundredth of the
    largest and more, a thousand times what the test above allows."""
    monkeypatch.setattr(ssd, "carry_states",
                        lambda local, decay: jnp.zeros_like(local))
    logits = jax.jit(lambda p: both["model"].apply(
        {"params": p, "buffers": both["buffers"]}, both["inputs"],
        train=True, mutable=["intermediates"])[0])(both["params"])
    want = both["logits"]
    assert float(jnp.max(jnp.abs(logits - want))) > 1e-2 * float(
        jnp.max(jnp.abs(want)))


# -- the router's new kinds and the leaf without a gradient -------------------
def test_the_bias_steers_the_choice_and_not_the_weights():
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0]])
    s = jax.nn.sigmoid(logits)
    w, e, scores = route_sigmoid(logits, 2, True, scale=2.5)
    np.testing.assert_array_equal(e, [[0, 1], [0, 1]])   # ties: the lower
    np.testing.assert_allclose(scores, s)
    np.testing.assert_allclose(w[0], 2.5 * s[0, :2] / s[0, :2].sum(),
                               rtol=1e-6)
    # a bias lifts expert 3 over expert 1: chosen, at its own score's weight
    w, e, _ = route_sigmoid(logits, 2, True,
                            bias=jnp.array([0.0, 0.0, 0.0, 0.5]), scale=2.5)
    np.testing.assert_array_equal(e[0], [0, 3])
    np.testing.assert_allclose(w[0], 2.5 * s[0, [0, 3]] / s[0, [0, 3]].sum(),
                               rtol=1e-6)
    # not renormalised: the scores themselves
    np.testing.assert_allclose(route_sigmoid(logits, 2, False)[0][0],
                               s[0, :2])


@pytest.fixture(scope="module")
def stepped(small):
    """Two steps of the trainer's own step on ``small``, built as the
    benchmark builds it (``hand_out``): the state before, after one step,
    and both steps' metrics."""
    model, tx = small["model"], optax.adamw(1e-5, weight_decay=0.1)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0))
    state = state.replace(params=small["params"])
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    step = make_lm_train_step(model, tx, mesh, seq_axis=None, donate=False,
                              hand_out=("expert_choice",))
    state = step.place_state(state)
    batch = (small["inputs"], small["targets"], jax.random.PRNGKey(1))
    after, first = step(state, *batch)
    _, second = step(after, *batch)
    return state, after, first, second


def test_the_correction_bias_has_no_gradient_and_moves_by_the_rule(
        small, stepped):
    """Through the trainer's own step: the biases ride outside ``params``
    (no gradient, no moments, no decay), start at zero, and after one step
    a layer's are ``0.001 sign(mean load - load_e)`` over all 16 experts,
    the loads counted on the step's tokens (the reference counts the same
    loads: its choices at no bias)."""
    state, after, first, second = stepped
    assert jax.tree.leaves(state.batch_stats) and not any(
        "router_bias" in path_names(p)
        for p, _ in jax.tree_util.tree_flatten_with_path(
            (state.params, state.opt_state))[0])
    assert float(first["layers"]["router_bias_range"]) == 0.0
    load = np.asarray(jax.jit(lambda w, x: nemotron_h.forward(
        w, x, small["config"])[4])(small["weights"], small["inputs"]))[0]
    assert load.sum() == 2 * S * 3 and load.max() > load.mean()
    bias = after.batch_stats["backbone_block1"]["mixer"]["router_bias"]
    np.testing.assert_allclose(bias, 1e-3 * np.sign(load.mean() - load),
                               atol=1e-9)
    # the second step's counters see the moved bias
    assert float(second["layers"]["router_bias_range"]) == pytest.approx(
        2e-3)


@pytest.mark.parametrize("act", ["relu2", "swiglu"])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(act):
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer and each
    the shared expert whole: their routed parts and the shared expert ONCE
    sum to what the reference's whole layer gives (extends
    ``test_keye_vl2.test_the_shares_of_a_routed_layer_add_up...`` to sigmoid
    scores, the scaling factor, ``relu2`` and the shared expert; ``swiglu``:
    the same with gated routed and shared experts against
    ``reference/xing4.py``'s layer, top-4 of 8 in shares of 2). Float32 sums
    in another order: 1e-5."""
    if act == "relu2":
        ref, z = nemotron_h, nemotron_h.sizes_of(CONFIG)
    else:
        from benchmark.families import lm_latent_hc_moe_train as latent
        from benchmark.reference import xing4 as ref

        z = ref.sizes_of({**load_json(
            ROOT + "/benchmark/configs/xing4.0-29b-a4b.json"),
            **latent.TINY["config"]})
    d, f, fs, width, held = z["d"], z["f"], z["fs"], z["width"], z["held"]
    keys = jax.random.split(jax.random.PRNGKey(11), 9)
    normal = lambda i, shape, std=0.1: std * jax.random.normal(  # noqa: E731
        keys[i], shape)
    # reference leaf -> the program's parameter, routed (a stack a held
    # expert) and shared
    routed = ({"w1": "w_up", "w2": "w_down"} if act == "relu2" else
              {"w1g": "w_gate", "w1u": "w_up", "w2": "w_down"})
    shared = ({"s1": "shared_up", "s2": "shared_down"} if act == "relu2" else
              {"sg": "shared_gate", "su": "shared_up", "sd": "shared_down"})
    down = ("w2", "s2", "sd")
    whole = {"router": normal(0, (d, width), 0.5)}
    whole.update({name: normal(i, (width, f, d) if name in down
                               else (width, d, f))
                  for i, name in enumerate(routed, start=1)})
    whole.update({name: normal(i, (fs, d) if name in down else (d, fs))
                  for i, name in enumerate(shared, start=4)})
    x = jax.random.normal(keys[8], (2, S, d))
    flat = x.reshape(-1, d)
    want = ref.experts(flat, whole, z, make_einsum("f32"),
                       held=(0, width))[0]
    if act == "relu2":
        alone = jnp.square(jax.nn.relu(flat @ whole["s1"])) @ whole["s2"]
    else:
        alone = (jax.nn.silu(flat @ whole["sg"]) * (flat @ whole["su"])
                 ) @ whole["sd"]
    total, assigned = alone, 0.0
    for first in range(0, width, held):
        layer = RoutedExperts(held, f, k=z["k"], router_width=width,
                              offset=first, act=act, dtype=jnp.float32,
                              score="sigmoid", scale=z["scale"],
                              shared_dim=fs)
        mine = slice(first, first + held)
        params = {"gate": {"kernel": whole["router"]}}
        params.update({p: whole[r][mine] for r, p in routed.items()})
        params.update({p: {"kernel": whole[r]} for r, p in shared.items()})
        part, mods = layer.apply({"params": params}, x,
                                 mutable=["intermediates"])
        counts = mods["intermediates"]["moe_counts"][0]
        assert float(counts["dropped"]) == 0.0
        assigned += float(counts["assignments_per_token"])
        total = total + (part.reshape(-1, d) - alone)
    assert assigned == pytest.approx(z["k"])    # every choice ran somewhere
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_an_ungated_experts_width_is_padded_to_the_products_tile():
    """960 hidden columns run as 1,024 (``grouped_pad``: the compiler's
    grouped products run an aligned width at twice the rate) and give what
    960 give: ``relu(0)^2`` meets zero rows of the down matrix. Widths a pad
    would inflate by more than an eighth, tile multiples and gated experts
    stay as they are."""
    from ddw_tpu.models.moe import grouped_experts, grouped_pad

    assert [grouped_pad(n) for n in (1856, 960, 576, 64, 1024, 768)] == [
        192, 64, 0, 0, 0, 0]
    d, f, e, t = 16, 960, 2, 24
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(keys[0], (t, d))
    w1 = 0.1 * jax.random.normal(keys[1], (e, d, f))
    w2 = 0.1 * jax.random.normal(keys[2], (e, f, d))
    local = jax.random.randint(keys[3], (t, 2), -1, e)   # -1: held elsewhere
    gates = jax.random.uniform(keys[4], (t, 2))

    def run(x, w1, w2):
        return grouped_experts(x, local, gates, [w1], w2, "relu2",
                               jnp.float32)[0]

    want = sum(jnp.where((local == i)[..., None], gates[..., None], 0.0).sum(1)
               * (jnp.square(jax.nn.relu(x @ w1[i])) @ w2[i])
               for i in range(e))
    np.testing.assert_allclose(run(x, w1, w2), want, atol=1e-5)
    assert "1024" in str(jax.make_jaxpr(run)(x, w1, w2))
    grads = jax.grad(lambda *a: jnp.sum(jnp.sin(run(*a))), (1, 2))(x, w1, w2)
    assert [g.shape for g in grads] == [w1.shape, w2.shape]


def count(jaxpr, names) -> int:
    """How many equations of ``jaxpr``, its sub-programs included, are of a
    primitive in ``names``."""
    return sum((eqn.primitive.name in names)
               + sum(count(sub, names)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_a_layer_chooses_its_experts_once_a_step(both, remat):
    """A block rematerialised whole keeps its choice of experts beside the
    first product it keeps (``expert_choice``, ``expert_hidden``): the kept
    rows lie in that choice's order, and a backward pass that chose again
    could, at a near tie rounded otherwise, lay its own rows against them
    (PERF.md section 6, PR 34). So the gradient's program holds one ``top_k``
    for each of the four expert layers, not two, and six grouped products."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": remat}))

    def loss(params):
        logits = model.apply({"params": params, "buffers": both["buffers"]},
                             both["inputs"], train=True)
        return lm_loss(logits, both["targets"])

    jaxpr = jax.make_jaxpr(jax.grad(loss))(both["params"]).jaxpr
    layers = CONFIG["hybrid_override_pattern"].count("E")
    assert count(jaxpr, ("top_k",)) == layers
    assert count(jaxpr, ("ragged_dot", "ragged_dot_general")) == 6 * layers


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_a_layer_sorts_its_assignments_once_a_step(small, score):
    """A block rematerialised whole keeps the sort beside the first product
    (``expert_sort``, ``expert_hidden``), whichever router chose: the
    backward pass runs the chunks of the dispatch the forward pass ran and
    lays its rows against the kept product whatever a second making of the
    router would say. So the gradient's program holds one ``sort`` a routed
    layer, and the dispatch's loops of two passes, forward and backward (the
    chunks' and the scatter-add's of the way back, each), not of three."""
    import dataclasses

    cfg = family._lm_cfg(small["config"], {"remat": "full"})
    if score == "softmax":
        cfg = dataclasses.replace(cfg, layer=dataclasses.replace(
            cfg.layer, router_score="softmax", router_bias_rate=0.0))
    model = build_lm(cfg)
    variables = {"params": small["params"]}
    if score == "sigmoid":
        variables["buffers"] = small["buffers"]

    def loss(params):
        logits = model.apply(dict(variables, params=params), small["inputs"],
                             train=True)
        return lm_loss(logits, small["targets"])

    jaxpr = jax.make_jaxpr(jax.grad(loss))(small["params"]).jaxpr
    layers = small["config"]["hybrid_override_pattern"].count("E")
    assert count(jaxpr, ("sort",)) == layers
    assert count(jaxpr, ("while",)) == 4 * layers
    assert count(jaxpr, ("top_k",)) == (1 if score == "sigmoid" else 2) * layers


# -- the reference's own plumbing ----------------------------------------------
def test_the_reference_follows_the_steps_own_choices_and_holds_them_to_its_own(
        small, stepped):
    """The choices the step hands out (``hand_out``), packed as a run of the
    cell packs them, are the reference's own at float32 (margin and
    misplaced share zero, same loss); a choice off by one rank shows in both
    numbers; and a step that was not asked hands out nothing."""
    config, weights, inputs = small["config"], small["weights"], small["inputs"]
    chosen = stepped[2]["handed"]["expert_choice"]
    assert chosen.shape == (1, 2 * S, 3) and "handed" in stepped[3]
    handed = nemotron_h.attach_choices(inputs, chosen.reshape(1, 2, S, 3))
    assert handed.shape == (2, S + 1 * S * 3)
    tokens, choices = nemotron_h.split_choices(handed, config, S)
    np.testing.assert_array_equal(tokens, inputs)
    assert choices.shape == (1, 2, S, 3)
    margins = jax.jit(lambda w, x: nemotron_h.choice_margins(w, x, S, config))
    assert {k: float(v) for k, v in margins(weights, handed).items()} == {
        "expert_choice_margin": 0.0, "experts_misplaced_share": 0.0}
    loss = jax.jit(lambda w, x, y: nemotron_h.loss(w, x, y, config))
    assert float(loss(weights, handed, small["targets"])) == pytest.approx(
        float(loss(weights, inputs, small["targets"])), rel=1e-6)
    faulty = jax.jit(lambda w, x: nemotron_h.own_choices(
        w, x, config, shift=1))(weights, inputs)
    off = margins(weights, faulty)
    assert float(off["expert_choice_margin"]) > 0.0
    assert float(off["experts_misplaced_share"]) == pytest.approx(1 / 3)
    with pytest.raises(ValueError, match="hand_out"):
        from ddw_tpu.utils.config import LMCfg

        dense = build_lm(LMCfg(vocab_size=50, hidden=32, depth=1,
                               num_heads=4, mlp_dim=64))
        make_lm_train_step(dense, optax.adamw(1e-3), jax.sharding.Mesh(
            np.array(jax.devices()[:1]), ("data",)), seq_axis=None,
            hand_out=("expert_choice",))


@pytest.mark.parametrize("precision,least,most", [("f32", 0.0, 1e-4),
                                                  ("fp8", 0.02, 1.0)])
def test_the_lean_loop_is_the_plain_loop_and_holds_a_gradient(
        small, precision, least, most):
    """``reference/optim_lean.py`` gives ``optim.run_steps``'s numbers (same
    arithmetic, less kept on the device), and holds its first gradient
    against one handed in: the float32 reference's own is at no distance,
    the float8 control's a few hundredths and more."""
    from benchmark.reference import optim, optim_donating, optim_lean

    config = small["config"]
    fresh = lambda: jax.tree.map(jnp.copy, small["weights"])    # noqa: E731
    batches = [(np.asarray(small["inputs"]), np.asarray(small["targets"]))] * 2
    hyper = {"learning_rate": 1e-5, "weight_decay": 0.1}
    own = jax.jit(jax.grad(nemotron_h.make_loss(config)))(
        small["weights"], *batches[0])
    optim_donating.hold_against(jax.device_get(own))
    try:
        lean = optim_lean.run_steps(nemotron_h.make_loss(config, precision),
                                    fresh(), batches, hyper, 1)
        worst = optim_donating.DIRECTION_GAPS[0][0][1]
    finally:
        optim_donating.hold_against(None)
    assert least <= worst <= most
    if precision == "f32":
        plain = optim.run_steps(nemotron_h.make_loss(config), fresh(),
                                batches, hyper, 1)
        assert lean["losses"] == pytest.approx(plain["losses"], rel=1e-6)
        for kind in ("grad_norms", "delta_norms"):
            assert lean[kind].keys() == plain[kind].keys()
            for k, v in plain[kind].items():
                assert lean[kind][k] == pytest.approx(v, rel=1e-4, abs=1e-12)


# -- what stays as it was -------------------------------------------------------
def test_the_configuration_file_keeps_the_published_widths():
    c = PUBLISHED
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (2688, 32, 2, 128)
    assert (c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
            c["ssm_state_size"], c["conv_kernel"], c["chunk_size"]) == (
        64, 64, 8, 128, 4, 128)
    assert (c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_experts_per_tok"], c["routed_scaling_factor"],
            c["deployment"]["published_n_routed_experts"]) == (
        1856, 3712, 6, 2.5, 128)
    assert sorted(c["reduced"]) == ["hybrid_override_pattern",
                                    "n_routed_experts", "num_hidden_layers",
                                    "vocab_size"]
    dep = c["deployment"]
    first = dep["first_layer"]
    assert c["hybrid_override_pattern"] == "MEMEMEM*E" == dep[
        "published_hybrid_override_pattern"][first:first + 9]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (9, 8, 16_384)
    assert c["vocab_size"] * 8 == dep["published_vocab_size"]
    assert c["n_routed_experts"] * dep["chips_per_layer"] == dep[
        "published_n_routed_experts"]
    # softplus^-1 of a step inside the published range
    step = float(jax.nn.softplus(c["dt_bias_shift"]))
    assert c["time_step_min"] < step == pytest.approx(0.003, rel=1e-4)
    lm = family._lm_cfg(c, {"remat": "full"})
    assert (lm.pattern, lm.pos_encoding, lm.layer.router_width,
            lm.layer.ssm_heads * lm.layer.ssm_head_dim) == (
        "MEMEMEM*E", "none", 128, 4096)
    # 667.0 M parameters by the family's spec: 8.00 GB of arguments with
    # Adam's moments, 10.67 GB with the gradient
    count = sum(int(np.prod(shape)) for shape, _ in
                nemotron_h.weight_spec(c).values())
    assert count == 666_962_944


def test_required_flops_a_token_of_the_cell():
    per = family.layer_params(PUBLISHED)
    assert per == {"M": 38_707_200, "*": 23_396_352,
                   "E": 19_955_712 + 344_064 + 0.375 * 9_977_856}
    assert family.scan_flops_per_token(PUBLISHED) == 3_407_872
    want = (6 * (4 * 38_707_200 + 23_396_352 + 4 * 24_041_472 + 44_040_192)
            + 12 * 4096 * 8192 + 4 * 3 * 3_407_872)
    assert family.required_flops_per_item(PUBLISHED, 8192) == want
    assert want == 2_354_135_040            # 784.7 MFLOP forward, times 3
    assert want * 16_384 / 1e12 == pytest.approx(38.6, abs=0.05)


@pytest.mark.parametrize("spec_kw,cfg_kw", [
    ({}, {}),
    ({"norm": "rmsnorm", "bias": False, "mlp": "swiglu"},
     {"pos_encoding": "rope"}),
    ({"norm": "rmsnorm", "bias": False, "mlp": "swiglu",
      "experts_per_token": 2, "router_width": 8},
     {"pos_encoding": "rope", "num_experts": 4})])
def test_a_model_without_a_pattern_is_the_program_it_was(spec_kw, cfg_kw):
    """The GPT-2 and ViT block (the default spec), a gated RMSNorm block and
    Keye's routed block lower to the same program text whether the new fields
    are left at their defaults or spelt out: nothing of this PR is in their
    step, and their state has no buffers."""
    from ddw_tpu.utils.config import LayerSpec, LMCfg

    def lowered(**extra):
        cfg = LMCfg(vocab_size=50, max_len=16, hidden=32, depth=2,
                    num_heads=4, mlp_dim=64, dtype="float32",
                    layer=LayerSpec(**spec_kw, **extra), **cfg_kw)
        model = build_lm(cfg)
        tx = optax.adamw(1e-3)
        state = init_lm_state(model, tx, jax.random.PRNGKey(0))
        assert state.batch_stats == {} and model.pattern == ""
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
        toks = jnp.zeros((2, 16), jnp.int32)
        return make_lm_train_step(model, tx, mesh, seq_axis=None).lower(
            state, toks, toks, jax.random.PRNGKey(0)).as_text()

    assert lowered() == lowered(router_score="softmax", router_scale=1.0,
                                router_bias_rate=0.0, shared_expert_dim=0,
                                ssm_heads=0, ssm_dt_shift=0.0)


def test_a_pattern_has_to_fit_the_depth_and_trains_only():
    from ddw_tpu.utils.config import LMCfg

    toks = jnp.zeros((1, 8), jnp.int32)
    bad = build_lm(LMCfg(vocab_size=50, hidden=32, depth=3, num_heads=4,
                         mlp_dim=64, pattern="M*"))
    with pytest.raises(ValueError, match="pattern"):
        bad.init(jax.random.PRNGKey(0), toks)
    with pytest.raises(ValueError, match="pattern"):
        build_lm(LMCfg(vocab_size=50, hidden=32, depth=2, num_heads=4,
                       mlp_dim=64, pattern="Mx")).init(
                           jax.random.PRNGKey(0), toks)
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
    with pytest.raises(NotImplementedError, match="M5"):
        model.clone(decode=True).init(jax.random.PRNGKey(0), toks)
