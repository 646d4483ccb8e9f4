"""A stack of sandwich-normed blocks run several times over ONE set of weights
(``LMCfg.passes``, ``LayerSpec.post_norm``), an exit a pass through the one
head with a learned gate (``LMCfg.exit_gate``) and the expected loss over the
exits (``TrainCfg.exit_entropy_weight``) — held against the plain float32
reference of the Ouro decoder (``benchmark/reference/ouro.py``, which imports
nothing of the program), at a size the CPU holds and the published RATIOS:
hidden 64 = 4 heads of 16, the MLP 2.75 times as wide, 2 layers, 4 passes,
S = 32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import lm_looped_train as family
from benchmark.harness.manifest import ROOT, load_json
from benchmark.harness.step_probe import path_names, program_tree
from benchmark.harness.weights import seed_key, seeded_weights
from benchmark.reference import optim_lean, ouro
from benchmark.reference.matmul import make_einsum
from benchmark.tools.ouro_faults import FAULTS, planted
from ddw_tpu.models import lm
from ddw_tpu.models.lm import build_lm, exit_distribution
from ddw_tpu.runtime.mesh import make_data_mesh
from ddw_tpu.train.lm_step import (exit_loss, exit_token_reading,
                                   init_lm_state, make_lm_train_step)
from ddw_tpu.train.step import make_optimizer
from ddw_tpu.utils.config import LayerSpec, LMCfg, TrainCfg

PUBLISHED = load_json(ROOT + "/benchmark/configs/ouro-2.6b.json")
CONFIG = {**PUBLISHED, **family.TINY["config"]}
S = 32
BETA = CONFIG["exit_entropy_weight"]
PASSES = CONFIG["total_ut_steps"]


def program_loss(model, inputs, targets, weight=BETA):
    """What the step descends, as ``train/lm_step.py`` puts it together."""
    def loss(params):
        readings = model.apply({"params": params}, inputs, train=True,
                               targets=targets,
                               token_reading=exit_token_reading)
        total, last, accuracy, terms = exit_loss(readings, weight)
        return total, (last, accuracy, terms, readings)
    return loss


@pytest.fixture(scope="module")
def seeded():
    """The seeded reference weights, a batch, the weights laid out as the
    program's tree, and the reference's terms and gradients on the batch
    (made once)."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
    weights = seeded_weights(seed_key(7), ouro.weight_spec(CONFIG))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, S + 1), 0,
                                CONFIG["vocab_size"])
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), inputs))
    params = program_tree(shapes["params"], family.leaf_map(CONFIG), weights)

    def reference(w):
        got = ouro.terms(w, inputs, targets, CONFIG)
        return got["total"], got

    (_, ref_terms), ref_grads = jax.jit(jax.value_and_grad(
        reference, has_aux=True))(weights)
    return dict(weights=weights, params=params, inputs=inputs,
                targets=targets, ref_terms=ref_terms, ref_grads=ref_grads)


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
def test_loss_exits_and_every_leafs_gradient_are_the_references(seeded, remat):
    """The total with its entropy term, the four exits' cross-entropies and
    shares, and every one of the leaves (the four norms of a block, the gate
    and its bias among them), with nothing kept and with a block
    rematerialised whole inside the passes' loop. Float32 both sides on the
    CPU, sums in another order: 2e-4 of a leaf's length."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": remat}))
    (total, (last, _, terms, _)), grads = jax.jit(jax.value_and_grad(
        program_loss(model, seeded["inputs"], seeded["targets"]),
        has_aux=True))(seeded["params"])
    ref = seeded["ref_terms"]
    assert float(total) == pytest.approx(float(ref["total"]), rel=1e-6)
    assert float(last) == pytest.approx(float(ref["last"]), rel=1e-6)
    assert float(last) != float(total)
    for t in range(PASSES):
        assert float(terms[f"exit_loss_{t + 1}"]) == pytest.approx(
            float(ref["exit_loss"][t]), rel=1e-6)
        assert float(terms[f"exit_share_{t + 1}"]) == pytest.approx(
            float(ref["exit_share"][t]), rel=1e-5)
    gaps = leaf_gaps(seeded, grads)
    assert set(gaps) == set(ouro.weight_spec(CONFIG))
    assert max(gaps.values()) < 2e-4, sorted(gaps.items(),
                                             key=lambda kv: -kv[1])[:5]
    if remat == "full":
        return
    # the reference's loss for the loop: the last exit's value, the total's
    # gradient
    value, g = jax.jit(jax.value_and_grad(ouro.make_loss(CONFIG)))(
        seeded["weights"], seeded["inputs"], seeded["targets"])
    assert float(value) == pytest.approx(float(ref["last"]), rel=1e-6)
    for leaf in ("gate.w", "l0.wq", "head.w"):
        np.testing.assert_allclose(g[leaf], seeded["ref_grads"][leaf],
                                   rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("chunk", [16, 24])
def test_an_exits_chunks_of_tokens_read_what_the_whole_row_reads(
        seeded, chunk, monkeypatch):
    """The exits' logits are made ``_EXIT_CHUNK`` tokens at a time: 64 tokens
    in four chunks of 16, and in three of 22 with two tokens filled in (24
    does not divide them), give the whole row's readings, total and
    gradients: never a whole row's logits because a count does not divide."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "full"}))
    loss = program_loss(model, seeded["inputs"], seeded["targets"])
    whole = jax.value_and_grad(loss, has_aux=True)(seeded["params"])
    monkeypatch.setattr(lm, "_EXIT_CHUNK", chunk)
    text = jax.jit(lambda p: loss(p)[0]).lower(seeded["params"]).as_text()
    rows = -(-64 // -(-64 // chunk))        # 16, 22: a chunk's logits
    assert f"tensor<{rows}x{CONFIG['vocab_size']}xf32>" in text
    chunked = jax.value_and_grad(loss, has_aux=True)(seeded["params"])
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7)


PLAIN = {
    "default": dict(layer=LayerSpec()),
    "rmsnorm_swiglu_rope": dict(
        pos_encoding="rope", layer=LayerSpec(norm="rmsnorm", bias=False,
                                             head_dim=16, mlp="swiglu")),
}


@pytest.mark.parametrize("kind", sorted(PLAIN))
def test_one_pass_no_gate_no_post_norm_is_todays_model(kind):
    """With the new fields at their defaults the parameter tree has today's
    names and no other, and the logits are, bit for bit, those of the blocks
    called once each in order, then the final norm and the head: the model
    as it was built before a stack could run twice."""
    cfg = LMCfg(vocab_size=128, max_len=64, hidden=64, depth=2, num_heads=4,
                mlp_dim=96, dtype="float32", **PLAIN[kind])
    assert (cfg.passes, cfg.exit_gate, cfg.layer.post_norm) == (1, False,
                                                                False)
    model = build_lm(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, 128)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    norm = "LayerNorm" if kind == "default" else "RMSNorm"
    mlp = {"fc1", "fc2"} if kind == "default" else {"gate", "up", "down"}
    top = {"tok_embed", "head", f"{norm}_0", "backbone_block0",
           "backbone_block1"} | ({"pos_embed"} if kind == "default" else set())
    assert set(params) == top
    assert set(params["backbone_block1"]) == {f"{norm}_0", f"{norm}_1",
                                              "attn"} | mlp
    logits = model.apply({"params": params}, tokens)

    x = params["tok_embed"]["embedding"][tokens]
    positions = None
    if kind == "default":
        x = x + params["pos_embed"][:S][None]
    else:
        positions = jnp.arange(S)
    for i in range(cfg.depth):
        block = lm.DecoderBlock(cfg.num_heads, cfg.mlp_dim, 0.0, jnp.float32,
                                max_len=cfg.max_len, layer=cfg.layer)
        x = block.apply({"params": params[f"backbone_block{i}"]}, x, False,
                        positions)
    normed = lm.layer_norm(cfg.layer).apply(
        {"params": params[f"{norm}_0"]}, x)
    head = normed @ params["head"]["kernel"]
    if kind == "default":
        head = head + params["head"]["bias"]
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(head))


def untied_terms(u: dict, tokens, targets):
    """The looped model with NOTHING shared: pass ``t`` reads its own copy of
    every layer, of the final norm, the head and the gate (``t<pass>.<leaf>``)
    — the arithmetic of ``passes x depth`` untied blocks, from the
    reference's own parts."""
    einsum, z = make_einsum("f32"), ouro.sizes_of(CONFIG)
    h = u["wte"][tokens]
    ce, lam = [], []
    for t in range(PASSES):
        own = lambda name: u[f"t{t}.{name}"]                  # noqa: E731
        for i in range(z["layers"]):
            h = ouro.block(h, {n: own(f"l{i}.{n}") for n in ouro.LEAVES}, z,
                           einsum)
        h = ouro.rms_norm(h, own("lnf.g"), z["eps"])
        ce.append(ouro.token_cross_entropy(h, own("head.w"), targets, einsum))
        lam.append(jax.nn.sigmoid(einsum("bsd,do->bso", h, own("gate.w"))[
            ..., 0] + own("gate.b")[0]))
    p = ouro.exit_distribution(jnp.stack(lam))
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * jnp.stack(ce), axis=0) - BETA * entropy)


def test_a_shared_weights_gradient_is_the_sum_over_its_uses(seeded):
    """A model of ``passes x depth`` untied blocks (and untied norms, heads
    and gates) started from copies of the seeded weights: its gradient, summed
    over the copies of a leaf, is the looped program's gradient of that leaf —
    the loop's backward pass carries the sum over the four uses."""
    w = seeded["weights"]
    untied = {"wte": w["wte"], **{f"t{t}.{k}": v for t in range(PASSES)
                                  for k, v in w.items() if k != "wte"}}
    g_untied = jax.jit(jax.grad(untied_terms))(untied, seeded["inputs"],
                                               seeded["targets"])
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "full"}))
    grads = jax.jit(jax.grad(lambda p: program_loss(
        model, seeded["inputs"], seeded["targets"])(p)[0]))(seeded["params"])
    mapping = family.leaf_map(CONFIG)
    worst = 0.0
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        key = mapping[path_names(path)]
        if key == "wte":
            summed = g_untied["wte"]
        else:
            uses = [g_untied[f"t{t}.{key}"] for t in range(PASSES)]
            # every use moves the loss: no copy's gradient is the sum
            if key != "gate.w" and key != "gate.b":
                assert all(float(jnp.linalg.norm(x)) > 0 for x in uses)
            summed = sum(uses)
        worst = max(worst, float(jnp.linalg.norm(g - summed.reshape(g.shape))
                                 / jnp.linalg.norm(summed)))
    assert worst < 2e-4
    # the last pass's gate is not read: its copy gets nothing
    assert float(jnp.linalg.norm(g_untied[f"t{PASSES - 1}.gate.w"])) == 0.0


def test_the_exit_distribution_by_hand_on_two_tokens():
    """Two tokens, four exits: ``p_1 = lam_1``, ``p_t = lam_t prod_{j<t}(1 -
    lam_j)``, the last exit takes what is left (its own gate is not read),
    and the four sum to 1."""
    lam = np.array([[0.5, 0.1], [0.5, 0.9], [0.2, 0.3], [0.7, 0.6]])
    logits = jnp.asarray(np.log(lam / (1 - lam)), jnp.float32)
    p = np.asarray(exit_distribution(logits))
    want = np.array([[0.5, 0.1], [0.25, 0.81], [0.05, 0.027], [0.2, 0.063]])
    np.testing.assert_allclose(p, want, rtol=1e-5)
    np.testing.assert_allclose(p.sum(0), [1.0, 1.0], rtol=1e-6)
    # the last exit's own gate changes nothing
    other = logits.at[-1].set(jnp.asarray([-3.0, 5.0]))
    np.testing.assert_array_equal(np.asarray(exit_distribution(other)), p)
    np.testing.assert_allclose(np.asarray(ouro.exit_distribution(
        jnp.asarray(lam, jnp.float32))), want, rtol=1e-5)


def test_the_entropy_terms_sign_and_weight():
    """``L = mean[sum_t p_t CE_t - beta H(p)]``: a more even distribution
    LOWERS the loss by ``beta`` times its entropy, and ``beta`` 0 leaves the
    expected cross-entropy alone."""
    lam = np.array([[0.5, 0.1], [0.5, 0.9], [0.2, 0.3], [0.7, 0.6]])
    p = np.array([[0.5, 0.1], [0.25, 0.81], [0.05, 0.027], [0.2, 0.063]])
    ce = np.array([[3.0, 2.0], [2.5, 2.2], [2.0, 2.1], [1.5, 2.4]])
    readings = {"gate": jnp.asarray(np.log(lam / (1 - lam)),
                                    jnp.float32)[:, None],
                "ce": jnp.asarray(ce, jnp.float32)[:, None],
                "correct": jnp.zeros((4, 1, 2))}
    expected = (p * ce).sum(0).mean()
    entropy = -(p * np.log(p)).sum(0).mean()
    plain, last, _, terms = exit_loss(readings, 0.0)
    weighed = exit_loss(readings, 0.05)[0]
    assert float(plain) == pytest.approx(expected, rel=1e-5)
    assert float(weighed) == pytest.approx(expected - 0.05 * entropy,
                                           rel=1e-5)
    assert float(weighed) < float(plain)
    assert float(last) == pytest.approx(ce[-1].mean(), rel=1e-6)
    assert float(terms["exit_expected_loss"]) == pytest.approx(expected,
                                                               rel=1e-5)
    # a saturated gate: p of exactly 0 and 1, no NaN in the loss or its slope
    hard = dict(readings, gate=jnp.full((4, 1, 2), 200.0))
    value, slope = jax.value_and_grad(
        lambda g: exit_loss(dict(hard, gate=g), 0.05)[0])(hard["gate"])
    assert np.isfinite(float(value)) and np.all(np.isfinite(slope))


@pytest.fixture(scope="module")
def two_steps(seeded):
    """Two steps of the trainer's own step from the seeded weights: the
    metrics of each, the first moment after the first and the parameters
    after the second."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "none"}))
    traffic = load_json(ROOT + "/benchmark/traffic/lm_continue_s8192_b1.json")
    train_cfg = family._train_cfg(CONFIG, traffic, 0)
    tx = make_optimizer(train_cfg)
    mesh = make_data_mesh(devices=jax.devices()[:1])
    step = make_lm_train_step(
        model, tx, mesh, seq_axis=None, donate=False,
        exit_entropy_weight=train_cfg.exit_entropy_weight)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0)).replace(
        params=seeded["params"])
    batches = family.tiny_batches(CONFIG, family.TINY["traffic"], 5, 2)
    key = jax.random.PRNGKey(1)
    state1, m1 = step(state, *batches[0], key)
    state2, m2 = step(state1, *batches[1], key)
    return dict(model=model, hyper=family.hyper(traffic), batches=batches,
                state1=state1, state2=state2, metrics=(m1, m2))


def test_two_optimizer_steps_follow_the_reference_loop(seeded, two_steps):
    """The trainer's step, twice, against ``reference/optim_lean.py`` on the
    reference's loss: each step's reported loss (the last exit's), the first
    gradient by leaf (read from Adam's first moment, as the benchmark reads
    it) and the parameters' change after the second."""
    ref = optim_lean.run_steps(
        ouro.make_loss(CONFIG), jax.tree.map(jnp.copy, seeded["weights"]),
        [family.reference_batch(b) for b in two_steps["batches"]],
        two_steps["hyper"], 1)
    for got, want in zip(two_steps["metrics"], ref["losses"]):
        assert float(got["loss"]) == pytest.approx(want, rel=1e-5)
    mapping = family.leaf_map(CONFIG)
    mu = two_steps["state1"].opt_state
    moments = {path_names(p)[path_names(p).index("mu") + 1:]: x
               for p, x in jax.tree_util.tree_flatten_with_path(mu)[0]
               if "mu" in path_names(p)}
    for path, m in moments.items():
        assert float(jnp.linalg.norm(m)) / 0.1 == pytest.approx(
            ref["grad_norms"][mapping[path]], rel=2e-4)
    start = jax.tree_util.tree_flatten_with_path(seeded["params"])[0]
    end = jax.tree.leaves(two_steps["state2"].params)
    for (path, a), b in zip(start, end):
        assert float(jnp.linalg.norm(b - a)) == pytest.approx(
            ref["delta_norms"][mapping[path_names(path)]], rel=2e-3)


def test_the_steps_counters_are_the_hand_count(seeded, two_steps):
    """``exit_expected_passes`` (mean ``sum_t t p_t``, between 1 and 4) and
    ``exit_entropy`` (mean ``H(p)``, at most ln 4) in the step's metrics are
    what the exits' readings give by hand; ``loss`` and ``accuracy`` are the
    LAST exit's."""
    inputs, targets = two_steps["batches"][0]
    readings = two_steps["model"].apply({"params": seeded["params"]}, inputs,
                                        train=True, targets=targets,
                                        token_reading=exit_token_reading)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(readings["gate"], np.float64)))
    p = np.stack([lam[0], lam[1] * (1 - lam[0]),
                  lam[2] * (1 - lam[0]) * (1 - lam[1]),
                  (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    passes = (p * np.arange(1, 5)[:, None, None]).sum(0).mean()
    entropy = -(p * np.log(p)).sum(0).mean()
    got = two_steps["metrics"][0]
    layers = {k: float(v) for k, v in got["layers"].items()}
    assert layers["exit_expected_passes"] == pytest.approx(passes, rel=1e-5)
    assert 1.0 < layers["exit_expected_passes"] < 4.0
    assert layers["exit_entropy"] == pytest.approx(entropy, rel=1e-5)
    assert 0.0 < layers["exit_entropy"] <= np.log(4.0)
    ce = np.asarray(readings["ce"], np.float64)
    assert float(got["loss"]) == pytest.approx(ce[-1].mean(), rel=1e-6)
    assert float(got["accuracy"]) == pytest.approx(
        float(np.asarray(readings["correct"])[-1].mean()), abs=1e-7)
    assert layers["exit_loss_1"] == pytest.approx(ce[0].mean(), rel=1e-6)
    assert sum(layers[f"exit_share_{t}"] for t in range(1, 5)) == (
        pytest.approx(1.0, rel=1e-5))


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_moves_the_comparison_far_beyond_the_sound_gap(seeded,
                                                                       fault):
    """``benchmark/tools/ouro_faults.py``'s three, at the tiny size: each
    moves the worst leaf's gradient, or the exits' shares, a hundred times
    the sound run's gap and more."""
    model = build_lm(family._lm_cfg(CONFIG, {"remat": "full"}))
    run = lambda: jax.jit(jax.value_and_grad(program_loss(       # noqa: E731
        model, seeded["inputs"], seeded["targets"]), has_aux=True))(
            seeded["params"])
    (_, (_, _, terms, _)), grads = run()
    share_gap = lambda terms: max(                              # noqa: E731
        abs(float(terms[f"exit_share_{t + 1}"])
            - float(seeded["ref_terms"]["exit_share"][t]))
        for t in range(PASSES))
    sound = max(leaf_gaps(seeded, grads).values())
    sound_share = share_gap(terms)
    assert sound < 2e-4 and sound_share < 1e-6
    with planted(fault):
        (_, (_, _, terms, _)), grads = run()
    moved = max(leaf_gaps(seeded, grads).values())
    assert moved > 100 * sound
    if fault == "uniform_exit":
        assert share_gap(terms) > 100 * max(sound_share, 1e-6)
        assert float(terms["exit_entropy"]) == pytest.approx(np.log(4.0))
    # and the program is sound again once the fault is taken out
    assert max(leaf_gaps(seeded, run()[1]).values()) == pytest.approx(sound)


def test_the_lowered_step_holds_a_blocks_attention_once_a_layer():
    """The passes are one loop over broadcast weights: the text of the loss
    and its gradient, as lowered, is the same program whether the stack runs
    twice or four times — two loops (the passes forward, and back), each
    block's attention scores made in it once forward, once again under
    ``remat`` and once transposed, not once a pass (the flash kernels' custom
    calls on the chip are then 6 + 6 and not 24 + 24)."""
    import re

    def counts(passes):
        config = dict(CONFIG, total_ut_steps=passes)
        model = build_lm(family._lm_cfg(config, {"remat": "full"}))
        tokens = jnp.zeros((1, S), jnp.int32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens))["params"]
        text = jax.jit(jax.grad(lambda p, x: exit_loss(model.apply(
            {"params": p}, x, train=True, targets=x,
            token_reading=exit_token_reading), BETA)[0])).lower(
                params, tokens).as_text(debug_info=True)
        scores = [len(re.findall(
            rf"backbone_block{i}/attn/attention/bhqk", text))
            for i in range(config["num_hidden_layers"])]
        return (text.count("stablehlo.while"), text.count("dot_general"),
                scores)

    two, four = counts(2), counts(4)
    assert two == four
    # the passes forward and back, and inside each the exits' chunks of tokens
    assert two[0] == 4 and min(two[2]) > 0 and len(set(two[2])) == 1


REFUSED = {
    "decode": (dict(), dict(decode=True)),
    "ring": (dict(), dict(seq_axis="seq")),
    "lora": (dict(lora_rank=2), dict()),
    "pattern": (dict(pattern="**", layer=LayerSpec(norm="rmsnorm")), dict()),
    "streams": (dict(layer=LayerSpec(norm="rmsnorm", hyper_streams=2)),
                dict()),
    "mtp": (dict(mtp_depth=1), dict()),
    "experts": (dict(num_experts=2), dict()),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
@pytest.mark.parametrize("looped", ["passes", "exit_gate"])
def test_what_a_looped_stack_cannot_do_yet_raises(what, looped):
    """``passes > 1`` or an ``exit_gate`` under ``decode``, a sequence ring,
    LoRA, a ``pattern``, streams, the MTP module or experts: refused by name
    (ROADMAP M11), never run wrong."""
    fields, bound = REFUSED[what]
    cfg = LMCfg(vocab_size=64, max_len=32, hidden=32, depth=2, num_heads=2,
                mlp_dim=64, dtype="float32", passes=2,
                exit_gate=looped == "exit_gate", **fields)
    model = build_lm(cfg, seq_axis=bound.get("seq_axis")).clone(
        decode=bound.get("decode", False))
    with pytest.raises(NotImplementedError, match="ROADMAP M11"):
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                          jnp.zeros((1, 8), jnp.int32)))


def test_the_trainer_refuses_the_loop_across_pipeline_stages():
    from ddw_tpu.train.lm_trainer import LMTrainer

    cfg = LMCfg(vocab_size=64, max_len=32, hidden=32, depth=2, num_heads=2,
                mlp_dim=64, passes=2)
    with pytest.raises(NotImplementedError, match="ROADMAP M11"):
        LMTrainer(cfg, TrainCfg(pipeline_stages=2))
    # the sandwich norm alone: not under decode, not in a pattern's blocks
    for fields, bound in ((dict(), dict(decode=True)),
                          (dict(pattern="**"), dict())):
        spec = LayerSpec(norm="rmsnorm", post_norm=True)
        model = build_lm(LMCfg(vocab_size=64, max_len=32, hidden=32, depth=2,
                               num_heads=2, mlp_dim=64, layer=spec,
                               **fields)).clone(**bound)
        with pytest.raises(NotImplementedError, match="ROADMAP M2"):
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                              jnp.zeros((1, 8), jnp.int32)))
    with pytest.raises(ValueError, match="at least 2"):
        jax.eval_shape(lambda: build_lm(LMCfg(exit_gate=True)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
