"""The layer spec's new kinds — RMSNorm, q/k norms, M-RoPE, attention over
keys an indexer chose, routed experts on a chip's share — held against the
plain float32 reference of the Keye-VL-2.0 decoder (``benchmark/reference/
keye_vl2.py``, which imports nothing of the program), at a size the CPU holds:
hidden 64, 4/2 heads of 16, 16 experts top-4 with 4 held, topk 8 at S = 32,
2 layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import lm_sparse_moe_train as family
from benchmark.harness.manifest import ROOT, load_json
from benchmark.harness.step_probe import path_names, program_tree
from benchmark.harness.weights import seed_key, seeded_weights
from benchmark.reference import keye_vl2
from benchmark.reference.matmul import make_einsum
from ddw_tpu.models.lm import build_lm
from ddw_tpu.models.moe import RoutedExperts
from ddw_tpu.ops.flash_attention import flash_mha_seq_major
from ddw_tpu.ops.indexed_attention import indexed_attention, top_mask
from ddw_tpu.ops.rope import apply_rope
from ddw_tpu.train.lm_step import layer_terms, lm_loss

PUBLISHED = load_json(ROOT + "/benchmark/configs/keye-vl-2.0-30b-a3b.json")
CONFIG = dict(PUBLISHED, **family.TINY["config"])
TRAFFIC = {"remat": "none"}
S = 32


@pytest.fixture(scope="module")
def both():
    """The model, the seeded reference weights, the same weights laid out as
    the program's tree, and a batch."""
    model = build_lm(family._lm_cfg(CONFIG, TRAFFIC))
    weights = seeded_weights(seed_key(7), keye_vl2.weight_spec(CONFIG))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, S + 1), 0,
                                CONFIG["vocab_size"])
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens[:, :-1]))["params"]
    params = program_tree(shapes, family.leaf_map(CONFIG), weights)
    return model, weights, params, tokens[:, :-1], tokens[:, 1:]


def program_losses(model, params, inputs, targets):
    logits, mods = model.apply({"params": params}, inputs, train=True,
                               mutable=["intermediates"])
    return lm_loss(logits, targets), layer_terms(mods), logits


def by_reference_name(tree) -> dict:
    mapping = family.leaf_map(CONFIG)
    return {mapping[path_names(path)]: leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def reference_leaf(grads: dict, key: str):
    name, _, layer = key.partition("@")
    return grads[name][int(layer)] if layer else grads[name]


def test_logits_and_both_losses_agree_with_the_reference(both):
    model, weights, params, inputs, targets = both
    lm, terms, logits = program_losses(model, params, inputs, targets)
    ref_logits, ref_kl, ref_keys = keye_vl2.forward(weights, inputs, CONFIG)[:3]
    ref_lm, _ = keye_vl2.losses(weights, inputs, targets, CONFIG)
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5)
    np.testing.assert_allclose(lm, ref_lm, rtol=1e-6)
    np.testing.assert_allclose(terms["indexer_kl"], ref_kl, rtol=1e-4)
    np.testing.assert_allclose(terms["keys_per_query"], ref_keys, rtol=1e-6)
    assert float(terms["indexer_kl"]) > 0 and float(terms["moe_dropped"]) == 0


def test_every_leafs_gradient_agrees_with_the_reference(both):
    model, weights, params, inputs, targets = both

    def total(p):
        lm, terms, _ = program_losses(model, p, inputs, targets)
        return lm + terms["indexer_kl"]

    grads = by_reference_name(jax.grad(total)(params))
    ref = jax.grad(keye_vl2.make_loss(CONFIG))(weights, inputs, targets)
    assert len(grads) == 3 + 17 * CONFIG["num_hidden_layers"]
    for key, g in grads.items():
        want = reference_leaf(ref, key).reshape(g.shape)
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 0, key
        np.testing.assert_allclose(g, want, atol=2e-4 * scale, err_msg=key)


INDEXER = ("idx.wq", "idx.wk", "idx.ww", "idx.kn.g", "idx.kn.b")


def test_the_indexer_learns_from_the_kl_term_alone_and_nothing_else_does(both):
    model, _, params, inputs, targets = both
    of_lm = by_reference_name(jax.grad(
        lambda p: program_losses(model, p, inputs, targets)[0])(params))
    of_kl = by_reference_name(jax.grad(
        lambda p: program_losses(model, p, inputs, targets)[1]["indexer_kl"]
    )(params))
    for key in of_lm:
        indexer = key.split("@")[0].removeprefix("blk.") in INDEXER
        quiet, loud = (of_lm, of_kl) if indexer else (of_kl, of_lm)
        assert float(jnp.max(jnp.abs(quiet[key]))) == 0.0, key
        assert float(jnp.max(jnp.abs(loud[key]))) > 0.0, key


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-3, 4-7, 8-11, 12-15 of one layer: what they
    add to the residual sums to what the reference's whole layer adds."""
    z = keye_vl2.sizes_of(CONFIG)
    d, f, width, held = z["d"], z["f"], z["width"], z["held"]
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    whole = {"moe.router": 0.5 * jax.random.normal(keys[0], (d, width)),
             "moe.gate": 0.1 * jax.random.normal(keys[1], (width, d, f)),
             "moe.up": 0.1 * jax.random.normal(keys[2], (width, d, f)),
             "moe.down": 0.1 * jax.random.normal(keys[3], (width, f, d))}
    x = jax.random.normal(keys[4], (2, S, d))
    want = keye_vl2.routed_experts(x.reshape(-1, d), whole, z,
                                   make_einsum("f32"), held=(0, width))[0]
    total, assigned = 0.0, 0.0
    for first in range(0, width, held):
        layer = RoutedExperts(held, f, k=z["k"], router_width=width,
                              offset=first, dtype=jnp.float32)
        mine = slice(first, first + held)
        part, mods = layer.apply(
            {"params": {"gate": {"kernel": whole["moe.router"]},
                        "w_gate": whole["moe.gate"][mine],
                        "w_up": whole["moe.up"][mine],
                        "w_down": whole["moe.down"][mine]}},
            x, mutable=["intermediates"])
        counts = mods["intermediates"]["moe_counts"][0]
        assert float(counts["dropped"]) == 0.0
        assigned += float(counts["assignments_per_token"])
        total = total + part
    assert assigned == pytest.approx(z["k"])    # every choice ran somewhere
    np.testing.assert_allclose(total.reshape(-1, d), want, atol=1e-5)


def test_no_token_is_dropped_when_every_choice_is_held_here():
    """A router that sends every token to held experts only fills the whole
    ``k * T`` buffer: nothing dropped, and the dense sum over the held experts
    is what comes out."""
    d, f, held, k, width = 16, 8, 4, 4, 16
    layer = RoutedExperts(held, f, k=k, router_width=width, offset=8,
                          dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, d))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    # every logit of an expert held elsewhere far below the held ones
    bias = jnp.where((jnp.arange(width) >= 8) & (jnp.arange(width) < 12),
                     0.0, -50.0)
    gate = jnp.zeros((d, width)).at[0].set(bias)
    x = x.at[..., 0].set(1.0)
    params = dict(params, gate={"kernel": gate + params["gate"]["kernel"]
                                * (jnp.arange(d) > 0)[:, None]})
    out, mods = layer.apply({"params": params}, x, mutable=["intermediates"])
    counts = mods["intermediates"]["moe_counts"][0]
    assert float(counts["assignments_per_token"]) == k
    assert float(counts["dropped"]) == 0.0
    xt = x.reshape(-1, d)
    probs = jax.nn.softmax(xt @ params["gate"]["kernel"])[:, 8:12]
    probs = probs / probs.sum(-1, keepdims=True)
    dense = sum(probs[:, e, None] * (
        (jax.nn.silu(xt @ params["w_gate"][e]) * (xt @ params["w_up"][e]))
        @ params["w_down"][e]) for e in range(held))
    np.testing.assert_allclose(out.reshape(-1, d), dense, atol=1e-5)


@pytest.mark.parametrize("k", [1, 2])
def test_the_sorted_dispatch_serves_one_and_two_choices_a_token(k):
    d, f, e = 16, 8, 4
    layer = RoutedExperts(e, f, k=k, act="gelu", dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 8, d))
    params = layer.init(jax.random.PRNGKey(3), x)["params"]
    assert set(params) == {"gate", "w_up", "w_down"}
    out = layer.apply({"params": params}, x)
    xt = x.reshape(-1, d)
    top_p, top_i = jax.lax.top_k(
        jax.nn.softmax(xt @ params["gate"]["kernel"]), k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    dense = sum(jnp.sum(jnp.where(top_i == i, top_p, 0.0), -1)[:, None]
                * (jax.nn.gelu(xt @ params["w_up"][i]) @ params["w_down"][i])
                for i in range(e))
    np.testing.assert_allclose(out.reshape(-1, d), dense, atol=1e-5)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_no_grouped_product_is_made_twice(both, remat):
    """The grouped products are the part of a step whose time follows the
    routing (PERF.md section 5), so a layer makes each once: the gate's and
    the up projection's as one, then the down product, and two products of
    the backward pass for each. A block rematerialised whole keeps the first
    product's output, and the backward pass needs no output of the second."""
    _, _, params, inputs, targets = both
    model = build_lm(family._lm_cfg(CONFIG, {"remat": remat}))

    def loss(p):
        ce, terms, _ = program_losses(model, p, inputs, targets)
        return ce + terms["indexer_kl"]

    def count(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name.startswith("ragged_dot")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += count(sub)
        return n

    assert count(jax.make_jaxpr(jax.grad(loss))(params).jaxpr) == (
        6 * CONFIG["num_hidden_layers"])


# once a tier: the XLA tiles at a size they alone take, the kernels at one
# block and at four
@pytest.mark.parametrize("impl,s,d,tile", [("xla", 32, 16, 16),
                                           ("pallas", 128, 64, 32),
                                           ("pallas", 256, 128, 64)])
def test_fewer_queries_than_topk_is_causal_attention(impl, s, d, tile):
    """While ``t < topk`` every earlier key is chosen, whatever the indexer
    says: the path then equals the causal attention the other cells run."""
    b, h, kv = 2, 4, 2
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    q = jax.random.normal(keys[0], (b, s, h, d))
    k, v = (jax.random.normal(kk, (b, s, kv, d)) for kk in keys[1:3])
    # 16 index heads: a pair whose every head's ReLU is shut scores exactly 0
    # and ties with its like; with 16 that is one pair in 65,536
    qi = jax.random.normal(keys[3], (b, s, 16, 8))
    ki = jax.random.normal(keys[4], (b, s, 8))
    wi = jax.random.normal(keys[5], (b, s, 16))
    out, _, chosen, choice = indexed_attention(q, k, v, qi, ki, wi,
                                               topk=2 * s, tile=tile,
                                               impl=impl)
    np.testing.assert_array_equal(choice[1], jnp.tril(jnp.ones((s, s), bool)))
    want = flash_mha_seq_major(q, jnp.repeat(k, h // kv, 2),
                               jnp.repeat(v, h // kv, 2), causal=True,
                               impl="xla")
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_array_equal(chosen, jnp.broadcast_to(
        jnp.arange(1, s + 1), (b, s)))
    # and with fewer allowed, each query keeps exactly its topk best
    _, _, chosen, _ = indexed_attention(q, k, v, qi, ki, wi, topk=8,
                                        tile=tile, impl=impl)
    np.testing.assert_array_equal(chosen[0], jnp.minimum(jnp.arange(s) + 1, 8))


def test_top_mask_is_the_k_largest_the_earlier_of_equals():
    x = jnp.round(jax.random.normal(jax.random.PRNGKey(9), (6, 96)) * 4) / 4
    x = x.at[0, :5].set(0.0).at[0, 5:9].set(-0.0).at[1, 7:].set(-jnp.inf)
    x = x.at[2].set(1.0)
    for k in (1, 8, 37, 96, 200):
        first = jnp.argsort(-x, axis=-1, stable=True)[:, :min(k, 96)]
        want = jnp.zeros(x.shape, bool).at[jnp.arange(6)[:, None], first].set(
            True)
        np.testing.assert_array_equal(top_mask(x, k), want)
        np.testing.assert_array_equal(keye_vl2.top_choice(x, k), want)


def test_mrope_with_equal_components_is_rope():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 3, 128))
    pos = jnp.arange(12) + 5
    plain = apply_rope(x, pos, seq_axis=1, theta=1e7)
    multi = apply_rope(x, jnp.broadcast_to(pos, (3, 12)), seq_axis=1,
                       theta=1e7, sections=(16, 24, 24))
    np.testing.assert_array_equal(plain, multi)
    # a component of its own turns its own pairs: 16..39 follow the second
    apart = apply_rope(x, jnp.stack([pos, 2 * pos, pos]), seq_axis=1,
                       theta=1e7, sections=(16, 24, 24))
    second = apply_rope(x, 2 * pos, seq_axis=1, theta=1e7)
    np.testing.assert_array_equal(apart[..., :32], plain[..., :32])
    np.testing.assert_array_equal(apart[..., 32:80], second[..., 32:80])
    np.testing.assert_array_equal(apart[..., 80:], plain[..., 80:])
    with pytest.raises(ValueError, match="frequency pairs"):
        apply_rope(x, jnp.broadcast_to(pos, (3, 12)), seq_axis=1,
                   sections=(16, 24, 8))


def test_required_flops_a_token_of_the_cell():
    assert family.keys_per_query(8192, 2048) == 1792.125
    assert family.matmul_params(PUBLISHED) == 4 * (
        18_874_368 + 2_260_992 + 262_144 + 1.0 * 4_718_592) + 2048 * 18_992
    assert family.required_flops_per_item(PUBLISHED, 8192) == (
        860_160_000 + 352_346_112 + 62_920_704) == 1_275_426_816


def test_the_configuration_file_keeps_the_published_widths():
    c = PUBLISHED
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (2048, 32, 4, 128)
    assert (c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["deployment"]["published_num_experts"]) == (768, 8, 128)
    assert c["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert c["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert c["rope_theta"] == 10_000_000
    assert sorted(c["reduced"]) == ["num_experts", "num_hidden_layers",
                                    "num_local_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 16, 18_992)
    assert c["vocab_size"] * 8 == c["deployment"]["published_vocab_size"]
    spec = family._lm_cfg(c, {"remat": "full"}).layer
    assert (spec.router_width, spec.index_topk, spec.mrope_section) == (
        128, 2048, (16, 24, 24))


def test_the_default_spec_is_the_block_the_lm_always_had():
    """No field of the spec set: the parameter tree of a GPT-2-style model is
    what it was (names, shapes, biases, the float32 LayerNorms)."""
    from ddw_tpu.utils.config import LMCfg

    model = build_lm(LMCfg(vocab_size=50, hidden=32, depth=1, num_heads=4,
                           mlp_dim=64))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    flat = {"/".join(path_names(p)): leaf.shape for p, leaf in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert flat == {
        "tok_embed/embedding": (50, 32), "pos_embed": (2048, 32),
        "LayerNorm_0/scale": (32,), "LayerNorm_0/bias": (32,),
        "head/kernel": (32, 50), "head/bias": (50,),
        "backbone_block0/LayerNorm_0/scale": (32,),
        "backbone_block0/LayerNorm_0/bias": (32,),
        "backbone_block0/LayerNorm_1/scale": (32,),
        "backbone_block0/LayerNorm_1/bias": (32,),
        "backbone_block0/attn/query/kernel": (32, 4, 8),
        "backbone_block0/attn/query/bias": (4, 8),
        "backbone_block0/attn/key/kernel": (32, 4, 8),
        "backbone_block0/attn/key/bias": (4, 8),
        "backbone_block0/attn/value/kernel": (32, 4, 8),
        "backbone_block0/attn/value/bias": (4, 8),
        "backbone_block0/attn/out/kernel": (4, 8, 32),
        "backbone_block0/attn/out/bias": (32,),
        "backbone_block0/fc1/kernel": (32, 64),
        "backbone_block0/fc1/bias": (64,),
        "backbone_block0/fc2/kernel": (64, 32),
        "backbone_block0/fc2/bias": (32,)}


def test_a_dense_gated_block_trains_and_counts_through_the_trainer():
    """The spec's kinds compose outside the drawn model too: RMSNorm + SwiGLU
    dense MLP + indexed attention through ``LMTrainer.fit``, the counters on
    the trainer's row."""
    from ddw_tpu.train.lm_trainer import LMTrainer
    from ddw_tpu.utils.config import LayerSpec, LMCfg, TrainCfg

    spec = LayerSpec(norm="rmsnorm", bias=False, mlp="swiglu",
                     attention="indexed", index_heads=2, index_head_dim=8,
                     index_topk=4, index_tile=8)
    cfg = LMCfg(vocab_size=31, max_len=32, hidden=32, depth=1, num_heads=2,
                mlp_dim=48, dtype="float32", pos_encoding="rope", layer=spec)
    corpus = np.random.RandomState(0).randint(0, 31, (12, 17)).astype(np.int32)
    trainer = LMTrainer(cfg, TrainCfg(batch_size=2, epochs=1, num_devices=1,
                                      learning_rate=1e-3))
    row = trainer.fit(corpus).history[0]
    assert np.isfinite(row["loss"]) and row["indexer_kl"] > 0
    assert 3.0 <= row["keys_per_query"] <= 16.0
    assert "moe_dropped" not in row
    names = set(trainer.model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"][
            "backbone_block0"])
    assert {"gate", "up", "down", "RMSNorm_0", "RMSNorm_1"} <= names


@pytest.fixture()
def handed(both):
    """The batch with the program's choices on it packed behind the ids, as
    the benchmark's ``ChoiceProbe`` keeps a followed step and
    ``reference_batch`` reads its choices once the window is over."""
    model, _, params, inputs, _ = both
    made = []
    probe = family.ChoiceProbe(lambda state, *a: ("stepped", a[1:]), model,
                               lambda shapes: made.append(shapes) or params)
    state = type("State", (), {"params": params})()
    assert probe(state, inputs, "targets", "rng") == (
        "stepped", ("targets", "rng"))
    assert not family._CHOSEN and not made      # nothing read in the step
    rows, _ = family.reference_batch((np.asarray(inputs), np.asarray(inputs)))
    assert len(made) == 1 and not family._FOLLOWED
    family._CHOSEN.clear()
    plain, _ = family.reference_batch((np.asarray(inputs), np.asarray(inputs)))
    assert plain.shape == inputs.shape      # rows nobody followed go plain
    return rows


def test_the_choice_probe_reads_the_programs_choices(both, handed):
    inputs = both[3]
    depth, k = CONFIG["num_hidden_layers"], CONFIG["num_experts_per_tok"]
    assert handed.shape == (2, S + depth * S * 1 + depth * S * k)
    tokens, (keys, experts) = keye_vl2.split_choices(handed, CONFIG, S)
    np.testing.assert_array_equal(tokens, inputs)
    assert keys.shape == (depth, 2, S, 1) and experts.shape == (depth, 2, S, k)
    np.testing.assert_array_equal(
        keye_vl2.unpack_bits(keys, S).sum(-1),
        jnp.broadcast_to(jnp.minimum(jnp.arange(S) + 1, 8), (depth, 2, S)))
    assert int(experts.min()) >= 0 and int(experts.max()) < 16
    mask = jax.random.uniform(jax.random.PRNGKey(1), (3, 70)) < 0.3
    np.testing.assert_array_equal(
        keye_vl2.unpack_bits(keye_vl2.pack_bits(mask), 70), mask)


def test_the_reference_follows_choices_handed_in_and_holds_them_to_its_own(
        both, handed):
    """In float32 on both sides the program's choices are the reference's
    own: handing them in changes nothing, and they lie at no distance. A key
    swapped for a worse one shows as that distance; a choice that is none (a
    key not yet seen) is infinitely far."""
    _, weights, _, inputs, targets = both
    loss = keye_vl2.make_loss(CONFIG)
    np.testing.assert_allclose(loss(weights, handed, targets),
                               loss(weights, inputs, targets), rtol=1e-6)
    margins = keye_vl2.choice_margins(weights, handed, S, CONFIG)
    assert float(margins["key_choice_margin"]) < 1e-4
    assert float(margins["expert_choice_margin"]) < 1e-4
    assert float(margins["keys_misplaced_share"]) == 0.0
    assert float(margins["experts_misplaced_share"]) == 0.0

    _, (keys, experts) = keye_vl2.split_choices(handed, CONFIG, S)
    chosen = keye_vl2.unpack_bits(keys, S)
    last = chosen[0, 0, S - 1]              # layer 0, row 0, the last query
    assert int(last.sum()) == 8 < S
    worse = last.at[jnp.argmax(last)].set(False).at[jnp.argmin(last)].set(True)

    def margins_of(chosen_keys):
        rows = keye_vl2.attach_choices(inputs, chosen_keys, experts)
        return keye_vl2.choice_margins(weights, rows, S, CONFIG)

    swapped = margins_of(chosen.at[0, 0, S - 1].set(worse))
    assert 1e-4 < float(swapped["key_choice_margin"]) < jnp.inf
    # one key of one query's 8, of (S - 7 + 8 * 7 / 16) * 8 / S keys a query
    assert 0 < float(swapped["keys_misplaced_share"]) < 1 / (2 * S * 4)
    unseen = chosen[0, 0, 3].at[3].set(False).at[9].set(True)
    assert float(margins_of(chosen.at[0, 0, 3].set(unseen))[
        "key_choice_margin"]) == jnp.inf
    # and an expert swapped for the least likely one
    rows = keye_vl2.attach_choices(
        inputs, chosen, experts.at[0, 0, 0, 0].set(
            (experts[0, 0, 0, 0] + 1) % 16))
    assert float(keye_vl2.choice_margins(weights, rows, S, CONFIG)[
        "expert_choice_margin"]) > 1e-4


@pytest.mark.parametrize("fault,which", [
    (dict(shift=(2, 0)), "keys"), (dict(shift=(0, 1)), "experts"),
    (dict(precision="fp8"), "both")])
def test_a_fault_planted_in_the_choice_shows_in_the_misplaced_share(
        both, fault, which):
    """``own_choices`` off by ``shift`` ranks is wrong by the least distance a
    wrong choice can be: the margin hardly moves, the share of misplaced
    choices is ``shift / k`` wherever a query has more than ``k`` keys to
    choose from. Scores in a lower precision misplace some of both."""
    _, weights, _, inputs, _ = both
    sound = keye_vl2.choice_margins(
        weights, keye_vl2.own_choices(weights, inputs, CONFIG), S, CONFIG)
    assert all(float(v) == 0.0 for v in sound.values())
    got = keye_vl2.choice_margins(
        weights, keye_vl2.own_choices(weights, inputs, CONFIG, **fault), S,
        CONFIG)
    late = (S - 8) / S          # queries with more than topk = 8 keys
    if which == "keys":
        assert float(got["keys_misplaced_share"]) == pytest.approx(
            2 / 8 * late, rel=0.2)
        assert float(got["experts_misplaced_share"]) < 0.1  # knock-on only
        assert 0 < float(got["key_choice_margin"]) < jnp.inf
    elif which == "experts":
        assert float(got["experts_misplaced_share"]) == pytest.approx(1 / 4)
        assert 0 < float(got["expert_choice_margin"]) < jnp.inf
    else:
        assert float(got["keys_misplaced_share"]) > 0
        assert float(got["experts_misplaced_share"]) > 0


@pytest.mark.parametrize("precision,least,most", [("f32", 0.0, 1e-4),
                                                  ("fp8", 0.02, 1.0)])
def test_the_reference_loop_holds_its_gradient_against_the_programs(
        both, precision, least, most):
    """``first_gradient`` reads the program's first gradient out of Adam's
    first moment under the reference's names and shapes; the reference's loop
    (``optim_donating.run_steps``) reports how far its own lies from it, and a
    control how far it lies from the reference's: nothing in float32, a few
    per cent of the worst leaf with the products' operands in float8."""
    import optax

    from benchmark.reference import optim_donating

    model, weights, params, inputs, targets = both

    def loss(p):
        lm, terms, _ = program_losses(model, p, inputs, targets)
        return lm + terms["indexer_kl"]

    grads = jax.grad(loss)(params)
    moments = optax.adam(1e-3).update(grads, optax.adam(1e-3).init(params))[1]
    held = family.first_gradient(moments, family.leaf_map(CONFIG),
                                 keye_vl2.weight_spec(CONFIG))
    assert {k: v.shape for k, v in held.items()} == {
        k: shape for k, (shape, _) in keye_vl2.weight_spec(CONFIG).items()}
    np.testing.assert_allclose(
        held["blk.attn.wq"][1].reshape(-1),
        np.asarray(by_reference_name(grads)["blk.attn.wq@1"]).reshape(-1),
        rtol=1e-5)

    optim_donating.hold_against(held, keep_reference=True)
    hyper = {"learning_rate": 1e-3, "weight_decay": 0.0}
    batch = [(np.asarray(inputs), np.asarray(targets))]
    optim_donating.run_steps(keye_vl2.make_loss(CONFIG, "f32"), weights,
                             batch, hyper, 1)
    optim_donating.run_steps(keye_vl2.make_loss(CONFIG, precision), weights,
                             batch, hyper, 1)
    sound, control = map(family.direction_gaps, optim_donating.DIRECTION_GAPS)
    optim_donating.hold_against(None)
    assert not optim_donating._HELD and not optim_donating.DIRECTION_GAPS
    assert sound["indexer_direction_gap"] <= sound["grad_direction_gap"] < 1e-4
    for gap in control.values():
        assert least <= gap <= most, control
    assert np.isnan(family.direction_gaps([])["grad_direction_gap"])
