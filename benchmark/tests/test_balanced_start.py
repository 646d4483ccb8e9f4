"""The correction biases a cell that continues pre-training starts from
(``router_bias_start: balanced``), at tiny sizes on the CPU: the fixed point
evens the loads, program and reference get the same biases, and ``correct``
still holds the program's choices to the reference's scores under them."""

import ast

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.families import lm_hybrid_ssm_moe_train as family
from benchmark.harness.manifest import ROOT, load_json
from benchmark.harness.weights import seed_key, seeded_weights
from benchmark.reference import nemotron_h
from benchmark.reference.keye_vl2 import shifted_choice

CONFIG = dict(load_json(ROOT + "/benchmark/configs/nemotron-3-nano-30b-a3b.json"),
              **family.TINY["config"])
CELL = "nemotron3nano_train_s8192"


def test_the_cells_configuration_starts_at_balance():
    assert CONFIG["router_bias_start"] == "balanced"


def test_the_fixed_point_evens_a_layers_loads():
    # 4,096 tokens, 32 experts, 6 a token; every expert offset as a random
    # router against a state's common component offsets it
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    scores = jax.nn.sigmoid(jax.random.normal(keys[0], (4096, 32))
                            + 0.5 * jax.random.normal(keys[1], (1, 32)))
    loads = lambda b: np.asarray(jnp.sum(  # noqa: E731
        shifted_choice(scores + b, 6, 0), axis=0))
    before = loads(0.0)
    assert before.max() / before.mean() > 1.5
    bias = jax.jit(lambda s: nemotron_h.balance(s, 6))(scores)
    after = loads(bias)
    assert after.sum() == 4096 * 6
    assert np.abs(after / after.mean() - 1).max() < 0.02
    assert abs(float(jnp.mean(bias))) < 1e-6


def test_every_routed_layer_is_balanced_under_the_layers_before_it():
    spec = nemotron_h.weight_spec(CONFIG)
    w = jax.jit(lambda k: seeded_weights(k, spec))(seed_key(2 ** 31 + 3))
    tokens = np.asarray(family.make_corpus(5, 8, 64, CONFIG["vocab_size"])
                        [:, :-1], np.int32)
    bias = jax.jit(lambda w, x: nemotron_h.balanced_bias(w, x, CONFIG))(
        w, tokens)
    layers = CONFIG["hybrid_override_pattern"].count("E")
    width = CONFIG["deployment"]["published_n_routed_experts"]
    assert bias.shape == (layers, width)
    at_zero = np.asarray(nemotron_h.forward(w, tokens, CONFIG)[4])
    at_balance = np.asarray(nemotron_h.forward(w, tokens, CONFIG,
                                               bias=bias)[4])
    mean = tokens.size * CONFIG["num_experts_per_tok"] / width
    # to two tokens an expert (512 tokens, 96 an expert: the cell's 2 % of
    # 768 is fifteen), where no bias leaves experts tens of tokens off
    assert np.abs(at_balance - mean).max() <= 2
    assert np.abs(at_zero - mean).max() > 10
    # the held experts' share is the expected one
    held = CONFIG["n_routed_experts"]
    assert at_balance[:, :held].sum(axis=1) / tokens.size == pytest.approx(
        CONFIG["num_experts_per_tok"] * held / width, abs=0.02)


def test_place_bias_puts_layer_j_into_the_programs_j_th_buffer():
    buffers = {f"backbone_block{i}": {"mixer": {"router_bias": jnp.zeros(
        (16,), jnp.float32)}} for i in (1, 3, 5, 8)}
    bias = np.arange(64, dtype=np.float32).reshape(4, 16)
    placed = family.place_bias(buffers, bias)
    for j, i in enumerate((1, 3, 5, 8)):
        leaf = placed[f"backbone_block{i}"]["mixer"]["router_bias"]
        np.testing.assert_array_equal(leaf, bias[j])
        assert leaf.dtype == jnp.float32
    with pytest.raises(ValueError):
        family.place_bias(buffers, bias[:3])
    # by the blocks' numbers, not by the order of their names: 10 after 8
    late = {f"backbone_block{i}": {"mixer": {"router_bias": jnp.zeros(16)}}
            for i in (10, 1, 8)}
    placed = family.place_bias(late, bias[:3])
    np.testing.assert_array_equal(
        placed["backbone_block10"]["mixer"]["router_bias"], bias[2])


def test_no_key_no_bias():
    config = {k: v for k, v in CONFIG.items() if k != "router_bias_start"}
    assert family.start_bias(config, family.TINY["traffic"], 3,
                             jax.devices()[:1]) is None


def test_a_run_starts_the_program_at_the_references_biases(capfd):
    """The whole run at tiny sizes: the program's first epoch reads the
    handed biases' range (the rule has moved them by 1e-3 a step since), the
    held experts get their expected share from the first epoch on, and the
    choices the step made lie on the reference's under the same biases."""
    out = bench_run.rehearse(CELL, 2 ** 31 + 11, 0.5, False, family.TINY)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["expert_choice_margin"] < 1e-3
    assert out["checks"]["experts_misplaced_share"] < 1e-3
    text = capfd.readouterr().out
    start = next(line for line in text.splitlines()
                 if line.startswith("router_bias_start balanced"))
    ranges = ast.literal_eval(start[start.index("["):start.index("]") + 1])
    rows = ast.literal_eval(next(
        line for line in text.splitlines()
        if line.startswith("counters by epoch "))[len("counters by epoch "):])
    steps = family.TINY["traffic"]["steps_per_epoch"]
    assert rows[0]["router_bias_range"] == pytest.approx(
        sum(ranges) / len(ranges), abs=2 * steps * 1e-3)
    expected = (CONFIG["num_experts_per_tok"] * CONFIG["n_routed_experts"]
                / CONFIG["deployment"]["published_n_routed_experts"])
    assert rows[0]["moe_assignments_per_token"] == pytest.approx(expected,
                                                                 abs=0.06)
