"""Latent attention with unequal head widths and YaRN's turn, a residual path
of four hyper-connected streams, a leading dense layer before sigmoid-routed
SwiGLU experts beside a shared one, and a multi-token-prediction module —
held against the plain float32 reference of the Xing4.0 decoder
(``benchmark/reference/xing4.py``, which imports nothing of the program), at a
size the CPU holds and the published RATIOS: hidden 64, 4 heads of nope 16 /
rope 8 / v 16 (2 : 1 : 2), latents 24 and 16, four streams, top-4 of 8 experts
with 2 held, 1 dense + 1 expert layer + the MTP module at S = 32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import lm_latent_hc_moe_train as family
from benchmark.harness.manifest import ROOT, load_json
from benchmark.harness.step_probe import path_names, program_tree
from benchmark.harness.weights import seed_key, seeded_weights
from benchmark.reference import xing4
from benchmark.tools.xing4_faults import FAULTS, planted
from ddw_tpu.models import lm
from ddw_tpu.models.lm import build_lm, generate
from ddw_tpu.ops import rope
from ddw_tpu.runtime.mesh import make_data_mesh
from ddw_tpu.train.lm_step import (init_lm_state, layer_terms, lm_loss,
                                   make_lm_train_step)
from ddw_tpu.train.step import make_optimizer
from ddw_tpu.utils.config import LayerSpec, LMCfg, TrainCfg

PUBLISHED = load_json(ROOT + "/benchmark/configs/xing4.0-29b-a4b.json")
CONFIG = {**PUBLISHED, **family.TINY["config"], "num_hidden_layers": 2}
S = 32
LAM = CONFIG["mtp_loss_weight"]


def program_loss(model, buffers, inputs, targets, weight=LAM):
    """What the step descends, as ``train/lm_step.py`` puts it together."""
    def loss(params):
        logits, mods = model.apply({"params": params, "buffers": buffers},
                                   inputs, train=True,
                                   mutable=["intermediates"])
        ahead = mods["intermediates"]["mtp_logits"][0]
        second = lm_loss(ahead[:, :-1], targets[:, 1:])
        return (lm_loss(logits, targets) + weight * second,
                (layer_terms(mods), second))
    return loss


@pytest.fixture(scope="module")
def both():
    """The model, the seeded reference weights, the same weights laid out as
    the program's tree, zeroed correction biases, a batch, and both sides'
    loss and gradients on it (made once: each test reads them)."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
    weights = seeded_weights(seed_key(7), xing4.weight_spec(CONFIG))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, S + 1), 0,
                                CONFIG["vocab_size"])
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), inputs))
    params = program_tree(shapes["params"], family.leaf_map(CONFIG), weights)
    buffers = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                           shapes["buffers"])
    (loss, (terms, second)), grads = jax.jit(jax.value_and_grad(
        program_loss(model, buffers, inputs, targets), has_aux=True))(params)

    def reference(w):
        main, ahead = xing4.losses(w, inputs, targets, CONFIG)
        return main + LAM * ahead, (main, ahead)

    (ref_loss, ref_parts), ref_grads = jax.jit(jax.value_and_grad(
        reference, has_aux=True))(weights)
    return dict(model=model, weights=weights, params=params, buffers=buffers,
                inputs=inputs, targets=targets, loss=loss, terms=terms,
                second=second, grads=grads, ref_loss=ref_loss,
                ref_parts=ref_parts, ref_grads=ref_grads)


def leaf_gaps(both, grads) -> dict:
    """Every leaf's ``|g - g_ref| / |g_ref|`` by the reference's name."""
    mapping = family.leaf_map(CONFIG)
    out = {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        ref = both["ref_grads"][mapping[path_names(path)]].reshape(g.shape)
        out[mapping[path_names(path)]] = float(
            jnp.linalg.norm(g - ref) / jnp.linalg.norm(ref))
    return out


def test_loss_and_every_leafs_gradient_are_the_references(both):
    """The total with the MTP term, both of its parts, and every one of the
    leaves (the hyper-connections' scalars and biases, both latents' norms,
    the shared rotary key's down-projection, ``eh_proj`` among them). Float32
    both sides on the CPU, sums in another order: 2e-4 of a leaf's length."""
    main, ahead = both["ref_parts"]
    assert float(both["loss"]) == pytest.approx(float(both["ref_loss"]),
                                                rel=1e-6)
    assert float(both["second"]) == pytest.approx(float(ahead), rel=1e-6)
    assert float(both["terms"]["hc_res_offdiag_share"]) == pytest.approx(
        3 / (3 + np.exp(CONFIG["hc_res_diag_start"])), abs=0.01)
    assert float(main) != float(both["ref_loss"])
    gaps = leaf_gaps(both, both["grads"])
    assert set(gaps) == set(xing4.weight_spec(CONFIG))
    assert max(gaps.values()) < 2e-4, sorted(gaps.items(),
                                             key=lambda kv: -kv[1])[:5]
    # the reference's loss for the loop: the main head's value, the total's
    # gradient
    value, g = jax.jit(jax.value_and_grad(xing4.make_loss(CONFIG)))(
        both["weights"], both["inputs"], both["targets"])
    assert float(value) == pytest.approx(float(main), rel=1e-6)
    np.testing.assert_allclose(g["m.weh"], both["ref_grads"]["m.weh"],
                               rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_moves_the_gradient_far_beyond_the_sound_gap(both,
                                                                     fault):
    """``benchmark/tools/xing4_faults.py``'s three, at the tiny size: with the
    mixing matrix the identity, without YaRN's factor in the softmax scale, or
    with ``lambda = 0`` some leaf's gradient is off by a hundred times the
    sound program's worst gap (and the MTP module's leaves by their whole
    length where its term is gone)."""
    weight = 0.0 if fault == "no_mtp_term" else LAM
    with planted(fault):
        model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
        grads = jax.jit(jax.grad(lambda p: program_loss(
            model, both["buffers"], both["inputs"], both["targets"],
            weight)(p)[0]))(both["params"])
    gaps = leaf_gaps(both, grads)
    assert max(gaps.values()) > 2e-2
    if fault == "no_mtp_term":
        assert gaps["m.weh"] == pytest.approx(1.0)


def test_one_stream_is_todays_residual_bit_for_bit():
    """``hyper_streams`` 0 and 1 are the same model: parameter names and
    outputs; and the hyper-connection's own read and write with ONE stream
    and unit coefficients give ``x + y`` to the last bit."""
    cfg = LMCfg(vocab_size=64, max_len=32, hidden=32, depth=2, num_heads=2,
                mlp_dim=64, dtype="float32")
    one = LMCfg(**{**cfg.__dict__, "layer": LayerSpec(hyper_streams=1)})
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    params = build_lm(cfg).init(jax.random.PRNGKey(1), tokens)
    same = build_lm(one).init(jax.random.PRNGKey(1), tokens)
    assert jax.tree.structure(params) == jax.tree.structure(same)
    np.testing.assert_array_equal(build_lm(cfg).apply(params, tokens),
                                  build_lm(one).apply(params, tokens))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 1, 32))
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 32))
    ones = jnp.ones((2, 16, 1))
    np.testing.assert_array_equal(lm.hyper_read(x, ones), x[:, :, 0])
    np.testing.assert_array_equal(
        lm.hyper_write(x, y, ones, ones[..., None])[:, :, 0], x[:, :, 0] + y)


def test_the_mixing_matrix_is_doubly_stochastic(both):
    """20 rounds on logits of unit spread: rows and columns sum to 1 within
    1e-4, entries positive, and two rounds are not enough; logits three times
    as wide are still a hundredth off after 20 (what ``hc_sinkhorn_error`` is
    there to show); the model's own counter at the seeded weights, whose
    logits are the diagonal constant and little else, is float32's
    rounding."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (64, 4, 4))
    off = lambda m: max(float(jnp.max(jnp.abs(jnp.sum(m, axis) - 1)))  # noqa: E731
                        for axis in (-1, -2))
    m = lm.sinkhorn(logits, CONFIG["hc_sinkhorn_iters"], CONFIG["hc_eps"])
    assert off(m) < 1e-4 and float(m.min()) > 0
    assert off(lm.sinkhorn(logits, 2, CONFIG["hc_eps"])) > 100 * off(m)
    assert off(lm.sinkhorn(3.0 * logits, CONFIG["hc_sinkhorn_iters"],
                           CONFIG["hc_eps"])) > 1e-2
    assert float(both["terms"]["hc_sinkhorn_error"]) < 1e-5


def test_yarn_angles_are_the_formula():
    """At a factor of 1 plain RoPE; at 64 over an original context of 4,096
    the published blend: pairs 0-10 of the 32 untouched, 23-31 turned 64 times
    slower, a linear ramp over 10-23; and the softmax scale's factor."""
    pos = jnp.arange(0, 4096, 37)
    for got, want in zip(rope.yarn_angles(pos, 64, 10000.0, 1.0, 32, 1, 4096),
                         rope.rope_angles(pos, 64, 10000.0)):
        np.testing.assert_array_equal(got, want)
    inv = np.asarray(rope.yarn_inv_freq(64, 10000.0, 64.0, 32, 1, 4096))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    turns = lambda t: 64 * np.log(4096 / (t * 2 * np.pi)) / (  # noqa: E731
        2 * np.log(10000.0))
    low, high = np.floor(turns(32)), np.ceil(turns(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, plain / 64 * ramp + plain * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv, xing4.yarn_inv_freq(
        xing4.sizes_of(PUBLISHED)), rtol=1e-6)
    cos, sin = rope.yarn_angles(pos, 64, 10000.0, 64.0, 32, 1, 4096)
    np.testing.assert_allclose(cos, np.cos(np.asarray(pos)[:, None] * inv),
                               atol=2e-4)
    assert sin.dtype == jnp.float32
    assert rope.yarn_softmax_factor(64.0) == pytest.approx(
        (0.1 * np.log(64) + 1) ** 2) and rope.yarn_softmax_factor(1.0) == 1.0
    assert xing4.softmax_scale(xing4.sizes_of(PUBLISHED)) == pytest.approx(
        192 ** -0.5 * 2.0047, rel=1e-4)


@pytest.fixture(scope="module")
def stepped():
    """One step through ``make_lm_train_step`` with bfloat16 first moments on
    rows of three tokens, with ``remat`` none and full: ``{remat: (model,
    state before, tokens, state after, metrics)}``."""
    runs = {}
    for remat in ("none", "full"):
        model = build_lm(family._lm_cfg(CONFIG, {"remat": remat}))
        tx = make_optimizer(TrainCfg(optimizer="adamw", learning_rate=1e-3,
                                     weight_decay=0.1,
                                     moment_dtype="bfloat16"))
        mesh = make_data_mesh(devices=jax.devices()[:1])
        state = init_lm_state(model, tx, jax.random.PRNGKey(0))
        step = make_lm_train_step(model, tx, mesh, seq_axis=None,
                                  donate=False, mtp_weight=LAM)
        tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 4), 0,
                                    CONFIG["vocab_size"])
        runs[remat] = (model, state, tokens) + step(
            state, tokens[:, :-1], tokens[:, 1:], jax.random.PRNGKey(0))
    return runs


def test_the_mtp_loss_on_two_positions_by_hand(stepped):
    """Rows of three tokens have two positions with a token after next:
    ``-log softmax(mtp logits[i])[t_{i+2}]`` at ``i = 0, 1``, a mean over the
    two and the rows. ``loss`` stays the main head's over all three; the
    step's ``mtp_loss`` is the hand's number and moves the parameters."""
    model, state, tokens, new, metrics = stepped["none"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    variables = {"params": state.params, "buffers": state.batch_stats}
    logits, mods = jax.jit(lambda v, x: model.apply(
        v, x, train=True, mutable=["intermediates"]))(variables, inputs)
    ahead = np.asarray(mods["intermediates"]["mtp_logits"][0], np.float64)
    by_hand = []
    for row in range(2):
        for i in (0, 1):
            z = ahead[row, i]
            by_hand.append(np.log(np.exp(z - z.max()).sum()) + z.max()
                           - z[int(tokens[row, i + 2])])
    assert float(metrics["layers"]["mtp_loss"]) == pytest.approx(
        np.mean(by_hand), rel=1e-5)
    assert float(metrics["loss"]) == pytest.approx(
        float(lm_loss(logits, targets)), rel=1e-5)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         new.params, state.params)
    assert moved["mtp_proj"]["kernel"] > 0


def test_a_step_keeps_its_counters_and_remat_changes_nothing(stepped):
    """The three counters of this model in the row, the correction biases of
    both routed blocks moved, the first moments bfloat16, and
    ``remat="full"`` (which keeps the four-stream input, the attention's
    output and the experts' products, and nothing of the hyper-connection)
    gives the step ``remat="none"`` gives."""
    new, metrics = stepped["full"][3:]
    assert {"hc_res_offdiag_share", "hc_sinkhorn_error", "mtp_loss",
            "moe_dropped"} <= set(metrics["layers"])
    assert float(metrics["layers"]["moe_dropped"]) == 0.0
    assert sorted(new.batch_stats) == ["backbone_block1", "mtp_block"]
    assert all(float(jnp.max(jnp.abs(b["moe"]["router_bias"]))) > 0
               for b in new.batch_stats.values())
    mu = new.opt_state.inner_state[0].mu
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(mu))
    # Adam's first step is 1e-3 a parameter whatever its gradient; the
    # hyper-connections' scalars and biases are left out: on six tokens at
    # the program's own start their gradient lies near Adam's epsilon, where
    # the last bits of a float32 sum decide the size of the step
    flat = jax.tree_util.tree_flatten_with_path
    for (path, a), (_, b) in zip(flat(stepped["none"][3].params)[0],
                                 flat(new.params)[0]):
        names = path_names(path)
        if not (names[-2].startswith("hc_") and names[-1] in ("alpha",
                                                              "bias")):
            np.testing.assert_allclose(a, b, atol=1e-4, err_msg=str(path))


def test_what_is_not_written_says_so():
    """Decode (no latent cache), a sequence ring and adapters raise for
    latent attention and for several streams alike."""
    cfg = family._lm_cfg(CONFIG, {"remat": "none"})
    model = build_lm(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    with pytest.raises(NotImplementedError, match="latent"):
        generate(model, params["params"], tokens, 2)
    with pytest.raises(NotImplementedError):
        build_lm(cfg, seq_axis="seq").init(jax.random.PRNGKey(0), tokens)
    plain = LMCfg(vocab_size=64, max_len=32, hidden=32, depth=1, num_heads=2,
                  mlp_dim=64, layer=LayerSpec(hyper_streams=4), lora_rank=2)
    with pytest.raises(NotImplementedError, match="hyper-connected"):
        build_lm(plain).init(jax.random.PRNGKey(0), tokens)
