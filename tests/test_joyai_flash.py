"""Latent attention turned by plain RoPE on ONE residual stream, a leading
dense layer before a sixteenth of sigmoid-routed SwiGLU experts beside a
shared one, and a multi-token-prediction module — held against the plain
float32 reference of the JoyAI-LLM-Flash decoder
(``benchmark/reference/joyai_flash.py``, which imports nothing of the
program), at a size the CPU holds and the published RATIOS: hidden 64, 4 heads
of nope 16 / rope 8 / v 16 (2 : 1 : 2), latents 48 and 16 (3 : 1), top-8 of 32
experts with 2 held (a sixteenth), 1 dense + 1 expert layer + the MTP module
at S = 32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import lm_latent_moe_train as family
from benchmark.harness.manifest import ROOT, load_json
from benchmark.harness.step_probe import path_names, program_tree
from benchmark.harness.weights import seed_key, seeded_weights
from benchmark.reference import joyai_flash, optim_lean
from benchmark.reference.matmul import make_einsum
from benchmark.reference.xing4 import experts as reference_experts
from benchmark.tools.joyai_flash_faults import FAULTS, planted
from ddw_tpu.models import lm
from ddw_tpu.models.lm import build_lm
from ddw_tpu.models.moe import RoutedExperts
from ddw_tpu.runtime.mesh import make_data_mesh
from ddw_tpu.train.lm_step import (init_lm_state, layer_terms, lm_loss,
                                   make_lm_train_step)
from ddw_tpu.train.step import make_optimizer
from ddw_tpu.utils.config import LayerSpec, TrainCfg

PUBLISHED = load_json(ROOT + "/benchmark/configs/joyai-llm-flash.json")
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
def seeded():
    """The seeded reference weights, a batch, zeroed correction biases, the
    weights laid out as the program's tree, and the reference's loss and
    gradients on the batch (made once)."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
    weights = seeded_weights(seed_key(7), joyai_flash.weight_spec(CONFIG))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, S + 1), 0,
                                CONFIG["vocab_size"])
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), inputs))
    params = program_tree(shapes["params"], family.leaf_map(CONFIG), weights)
    buffers = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                           shapes["buffers"])

    def reference(w):
        main, ahead = joyai_flash.losses(w, inputs, targets, CONFIG)
        return main + LAM * ahead, (main, ahead)

    (ref_loss, ref_parts), ref_grads = jax.jit(jax.value_and_grad(
        reference, has_aux=True))(weights)
    return dict(weights=weights, params=params, buffers=buffers,
                inputs=inputs, targets=targets, ref_loss=ref_loss,
                ref_parts=ref_parts, ref_grads=ref_grads)


def leaf_gaps(seeded, grads) -> dict:
    """Every leaf's ``|g - g_ref| / |g_ref|`` by the reference's name."""
    mapping = family.leaf_map(CONFIG)
    out = {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        ref = seeded["ref_grads"][mapping[path_names(path)]].reshape(g.shape)
        out[mapping[path_names(path)]] = float(
            jnp.linalg.norm(g - ref) / jnp.linalg.norm(ref))
    return out


@pytest.mark.parametrize("remat", ["none", "full"])
def test_loss_and_every_leafs_gradient_are_the_references(seeded, remat):
    """The total with the MTP term, both of its parts, and every one of the
    leaves (both latents' norms, the shared rotary key's down-projection,
    the router at its published width, ``eh_proj`` among them), with nothing
    kept and with a block rematerialised whole. Float32 both sides on the
    CPU, sums in another order: 2e-4 of a leaf's length."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": remat}))
    (loss, (terms, second)), grads = jax.jit(jax.value_and_grad(
        program_loss(model, seeded["buffers"], seeded["inputs"],
                     seeded["targets"]), has_aux=True))(seeded["params"])
    main, ahead = seeded["ref_parts"]
    assert float(loss) == pytest.approx(float(seeded["ref_loss"]), rel=1e-6)
    assert float(second) == pytest.approx(float(ahead), rel=1e-6)
    assert float(main) != float(seeded["ref_loss"])
    assert float(terms["moe_dropped"]) == 0.0
    gaps = leaf_gaps(seeded, grads)
    assert set(gaps) == set(joyai_flash.weight_spec(CONFIG))
    assert max(gaps.values()) < 2e-4, sorted(gaps.items(),
                                             key=lambda kv: -kv[1])[:5]
    if remat == "full":
        return
    # the reference's loss for the loop: the main head's value, the total's
    # gradient
    value, g = jax.jit(jax.value_and_grad(joyai_flash.make_loss(CONFIG)))(
        seeded["weights"], seeded["inputs"], seeded["targets"])
    assert float(value) == pytest.approx(float(main), rel=1e-6)
    np.testing.assert_allclose(g["m.weh"], seeded["ref_grads"]["m.weh"],
                               rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_moves_the_gradient_far_beyond_the_sound_gap(seeded,
                                                                     fault):
    """``benchmark/tools/joyai_flash_faults.py``'s three, at the tiny size:
    with ``lambda = 0``, without the routed scaling factor, or with a rotary
    key of its own a head some leaf's gradient is off by a hundred times the
    sound program's worst gap (and the MTP module's leaves by their whole
    length where its term is gone)."""
    weight = 0.0 if fault == "no_mtp_term" else LAM
    with planted(fault):
        model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
        grads = jax.jit(jax.grad(lambda p: program_loss(
            model, seeded["buffers"], seeded["inputs"], seeded["targets"],
            weight)(p)[0]))(seeded["params"])
    gaps = leaf_gaps(seeded, grads)
    assert max(gaps.values()) > 2e-2
    if fault == "no_mtp_term":
        assert gaps["m.weh"] == pytest.approx(1.0)


def test_the_shares_of_the_routed_layer_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer of 16
    (top-4, sigmoid scores, scaling 2.5) and each the shared expert whole:
    every share's output is the reference's for that share, and the routed
    parts with the shared expert ONCE sum to what the reference's whole layer
    gives. Float32 sums in another order: 1e-5."""
    z = {**joyai_flash.sizes_of(CONFIG), "width": 16, "held": 4, "k": 4}
    d, f, fs, width, held = z["d"], z["f"], z["fs"], z["width"], z["held"]
    keys = jax.random.split(jax.random.PRNGKey(11), 8)
    normal = lambda i, shape, std=0.1: std * jax.random.normal(  # noqa: E731
        keys[i], shape)
    whole = {"router": normal(0, (d, width), 0.5),
             "w1g": normal(1, (width, d, f)), "w1u": normal(2, (width, d, f)),
             "w2": normal(3, (width, f, d)), "sg": normal(4, (d, fs)),
             "su": normal(5, (d, fs)), "sd": normal(6, (fs, d))}
    x = jax.random.normal(keys[7], (2, S, d))
    flat, einsum = x.reshape(-1, d), make_einsum("f32")
    want = reference_experts(flat, whole, z, einsum, held=(0, width))[0]
    alone = (jax.nn.silu(flat @ whole["sg"]) * (flat @ whole["su"])
             ) @ whole["sd"]
    total, assigned = alone, 0.0
    for first in range(0, width, held):
        layer = RoutedExperts(held, f, k=z["k"], router_width=width,
                              offset=first, act="swiglu", dtype=jnp.float32,
                              score="sigmoid", scale=z["scale"],
                              shared_dim=fs)
        mine = slice(first, first + held)
        params = {"gate": {"kernel": whole["router"]},
                  "w_gate": whole["w1g"][mine], "w_up": whole["w1u"][mine],
                  "w_down": whole["w2"][mine],
                  "shared_gate": {"kernel": whole["sg"]},
                  "shared_up": {"kernel": whole["su"]},
                  "shared_down": {"kernel": whole["sd"]}}
        part, mods = layer.apply({"params": params}, x,
                                 mutable=["intermediates"])
        share = {**whole, "w1g": whole["w1g"][mine],
                 "w1u": whole["w1u"][mine], "w2": whole["w2"][mine]}
        np.testing.assert_allclose(
            part.reshape(-1, d), reference_experts(
                flat, share, z, einsum, held=(first, held))[0], atol=1e-5)
        counts = mods["intermediates"]["moe_counts"][0]
        assert float(counts["dropped"]) == 0.0
        assigned += float(counts["assignments_per_token"])
        total = total + (part.reshape(-1, d) - alone)
    assert z["scale"] == 2.5
    assert assigned == pytest.approx(z["k"])    # every choice ran somewhere
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_latent_attention_without_yarn_by_hand_on_two_positions():
    """One row of two tokens, at positions 3 and 50, through the program's
    latent attention, and the same by hand in numpy: pair ``i`` of the rotary
    part turned by ``t theta^(-2i/rope)`` at position ``t`` (interleaved
    pairs, theta 3.2e7 as published), ONE rotary key for all heads, scores times ``(nope +
    rope)^-1/2`` and nothing else. The hand's number with the scale of the
    nope part alone, or with the angles of another theta, is far off."""
    spec = family._lm_cfg(CONFIG, {"remat": "none"}).layer
    assert (spec.rope_scaling, spec.hyper_streams) == ("", 0)
    assert lm.yarn_of(spec) == () and spec.rope_theta == 32_000_000
    h, nope, rope, dv = 4, spec.qk_nope_dim, spec.qk_rope_dim, spec.v_head_dim
    d, kvr = CONFIG["hidden_size"], spec.kv_lora_rank
    attn = lm.CausalSelfAttention(num_heads=h, dtype=jnp.float32, layer=spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, d))
    positions = jnp.array([3, 50])
    params = attn.init(jax.random.PRNGKey(2), x, positions)["params"]
    got = np.asarray(attn.apply({"params": params}, x, positions))[0]

    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    u, at = np.asarray(x[0], np.float64), (3, 50)
    rms = lambda t, g: t / np.sqrt(np.mean(t * t, -1, keepdims=True)  # noqa: E731
                                   + spec.norm_eps) * g

    def by_hand(scale, theta):
        def turn(t, pos):       # t [..., rope]
            out = np.empty_like(t)
            for i in range(rope // 2):
                a = pos * theta ** (-2 * i / rope)
                out[..., 2 * i] = (t[..., 2 * i] * np.cos(a)
                                   - t[..., 2 * i + 1] * np.sin(a))
                out[..., 2 * i + 1] = (t[..., 2 * i] * np.sin(a)
                                       + t[..., 2 * i + 1] * np.cos(a))
            return out

        cq = rms(u @ p["q_down"]["kernel"], p["q_latent_norm"]["scale"])
        q = np.einsum("sr,rhe->she", cq, p["q_up"]["kernel"])
        down = u @ p["kv_down"]["kernel"]
        ckv = rms(down[:, :kvr], p["kv_latent_norm"]["scale"])
        k_nope = np.einsum("sr,rhe->she", ckv, p["k_up"]["kernel"])
        v = np.einsum("sr,rhe->she", ckv, p["v_up"]["kernel"])
        out = np.zeros((2, h, dv))
        for t in range(2):
            for head in range(h):
                q_rope = turn(q[t, head, nope:], at[t])
                logits = [(q[t, head, :nope] @ k_nope[j, head]
                           + q_rope @ turn(down[j, kvr:], at[j])) * scale
                          for j in range(t + 1)]
                w = np.exp(logits - np.max(logits))
                w /= w.sum()
                out[t, head] = sum(w[j] * v[j, head] for j in range(t + 1))
        return np.einsum("she,hed->sd", out, p["out"]["kernel"])

    want = by_hand((nope + rope) ** -0.5, spec.rope_theta)
    np.testing.assert_allclose(got, want, atol=1e-6)
    size = np.abs(want).max()
    assert np.abs(by_hand(nope ** -0.5, spec.rope_theta) - want).max() > (
        1e-3 * size)
    assert np.abs(by_hand((nope + rope) ** -0.5, 10000.0) - want).max() > (
        1e-3 * size)


@pytest.fixture(scope="module")
def machine(seeded):
    """The model, its step (AdamW 1e-3, float32 moments, the choices handed
    out) and a state at the seeded weights."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
    tx = make_optimizer(TrainCfg(optimizer="adamw", learning_rate=1e-3,
                                 weight_decay=0.1))
    mesh = make_data_mesh(devices=jax.devices()[:1])
    step = make_lm_train_step(model, tx, mesh, seq_axis=None, donate=False,
                              mtp_weight=LAM, hand_out=("expert_choice",))
    state = jax.jit(lambda: init_lm_state(model, tx, jax.random.PRNGKey(0)))(
        ).replace(params=seeded["params"])
    return model, step, state


def test_the_mtp_loss_on_one_stream_by_hand_and_its_block_routed_like_the_trunks(
        machine):
    """Rows of three tokens have two positions with a token after next:
    ``-log softmax(mtp logits[i])[t_{i+2}]`` at ``i = 0, 1``, a mean over the
    two and the rows, is the step's ``mtp_loss``; ``loss`` stays the main
    head's over all three. The module's block is a routed block like the
    trunk's: it hands out its own choices of 8 of 32, keeps a correction bias
    of its own that the step moves, and its term moves ``eh_proj``."""
    model, step, state = machine
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 4), 0,
                                CONFIG["vocab_size"])
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    new, metrics = step(state, inputs, targets, jax.random.PRNGKey(0))
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
    chosen = np.asarray(metrics["handed"]["expert_choice"])
    assert chosen.shape == (2, 6, 8)        # trunk's block, then the module's
    assert not np.array_equal(chosen[0], chosen[1])
    assert sorted(new.batch_stats) == ["backbone_block1", "mtp_block"]
    assert all(float(jnp.max(jnp.abs(b["moe"]["router_bias"]))) > 0
               for b in new.batch_stats.values())
    assert float(jnp.max(jnp.abs(new.params["mtp_proj"]["kernel"]
                                 - state.params["mtp_proj"]["kernel"]))) > 0


@pytest.fixture(scope="module")
def two_steps(machine):
    """Two optimizer steps of the program from the seeded weights on two
    batches: ``(states [before, after 1, after 2], metrics of both steps,
    batches)``."""
    _, step, state = machine
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 2, S + 1), 0,
                                CONFIG["vocab_size"])
    states, said = [state], []
    for batch in tokens:
        state, metrics = step(state, batch[:, :-1], batch[:, 1:],
                              jax.random.PRNGKey(0))
        states.append(state), said.append(metrics)
    return states, said, tokens


def test_two_steps_with_the_bias_moving_between_them_follow_the_lean_loop(
        seeded, two_steps):
    """The program's two steps against ``reference/optim_lean.py`` following
    the choices the steps handed out: both losses, the first gradient's norm
    and the parameters' change after step 2, leaf by leaf. Between the steps
    every routed block's correction bias moved by ``0.001 sign(mean load -
    load)`` of the loads the first step's own choices give, and the second
    step chose with it."""
    states, said, tokens = two_steps
    rate, width = CONFIG["router_bias_update_rate"], 32
    batches = []
    for batch, metrics in zip(tokens, said):
        chosen = np.asarray(metrics["handed"]["expert_choice"])
        batches.append((np.asarray(joyai_flash.attach_choices(
            np.asarray(batch[:, :-1]), chosen.reshape(2, 2, S, 8))),
            np.asarray(batch[:, 1:])))
    first = np.asarray(said[0]["handed"]["expert_choice"])
    for j, name in enumerate(("backbone_block1", "mtp_block")):
        load = np.bincount(first[j].reshape(-1), minlength=width)
        assert load.sum() == 2 * S * 8
        np.testing.assert_allclose(
            states[1].batch_stats[name]["moe"]["router_bias"],
            rate * np.sign(load.mean() - load), atol=1e-9)
    assert float(said[1]["layers"]["router_bias_range"]) == pytest.approx(
        2 * rate)
    lean = optim_lean.run_steps(
        joyai_flash.make_loss(CONFIG), jax.tree.map(jnp.copy,
                                                    seeded["weights"]),
        batches, {"learning_rate": 1e-3, "weight_decay": 0.1}, 2)
    assert [float(m["loss"]) for m in said] == pytest.approx(lean["losses"],
                                                            rel=1e-5)
    mapping = family.leaf_map(CONFIG)
    flat = jax.tree_util.tree_flatten_with_path
    mu = states[1].opt_state.inner_state[0].mu
    for (path, m), (_, a), (_, b) in zip(flat(mu)[0],
                                         flat(states[2].params)[0],
                                         flat(states[0].params)[0]):
        name = mapping[path_names(path)]
        # Adam's first moment after one step is a tenth of the gradient
        assert 10 * float(jnp.linalg.norm(m)) == pytest.approx(
            lean["grad_norms"][name], rel=1e-3), name
        assert float(jnp.linalg.norm(a - b)) == pytest.approx(
            lean["delta_norms"][name], rel=1e-3), name


def test_the_fullest_blocks_assignments_are_the_largest_and_not_under_the_mean(
        two_steps):
    """``moe_block_assignments_max`` is the larger of the two routed blocks'
    own assignments to the held experts a token (counted here from the choices
    the step handed out: experts 0 and 1 of 32 are held), and
    ``moe_assignments_per_token`` their mean."""
    for metrics in two_steps[1]:
        chosen = np.asarray(metrics["handed"]["expert_choice"])
        blocks = [(block < CONFIG["n_routed_experts"]).sum() / (2 * S)
                  for block in chosen]
        layers = metrics["layers"]
        assert float(layers["moe_block_assignments_max"]) == pytest.approx(
            max(blocks), rel=1e-6)
        assert float(layers["moe_assignments_per_token"]) == pytest.approx(
            np.mean(blocks), rel=1e-6)
        assert max(blocks) > np.mean(blocks)
        assert (float(layers["moe_block_assignments_max"])
                >= float(layers["moe_assignments_per_token"]))


def test_the_configuration_file_keeps_the_published_widths():
    """Every width of the source, the three cuts and nothing else under
    ``reduced``, and the deployment the cut stands for."""
    c = PUBLISHED
    assert (c["hidden_size"], c["q_lora_rank"], c["kv_lora_rank"],
            c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["intermediate_size"],
            c["moe_intermediate_size"], c["num_experts_per_tok"]) == (
                2048, 1536, 512, 32, 128, 64, 128, 7168, 768, 8)
    assert c["rope_scaling"] is None and c["rope_theta"] == 32_000_000
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    dep = c["deployment"]
    assert (c["num_hidden_layers"], dep["published_num_hidden_layers"]) == (
        5, 40)
    assert (c["n_routed_experts"], dep["published_n_routed_experts"],
            dep["chips_per_layer"]) == (16, 256, 16)
    assert (c["vocab_size"], dep["published_vocab_size"]) == (16160, 129280)
    assert c["first_k_dense_replace"] == 1
    spec = family._lm_cfg(c, {"remat": "full"})
    assert isinstance(spec.layer, LayerSpec)
    assert (spec.layer.router_width, spec.layer.expert_offset,
            spec.num_experts, spec.dense_layers, spec.mtp_depth) == (
                256, 0, 16, 1, 1)
