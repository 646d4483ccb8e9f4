"""Transformer LM + DPxSP train step: causality, SP equivalence, learning."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ddw_tpu.models.lm import TransformerLM
from ddw_tpu.parallel.sharding import LM_TP_RULES, make_sharded_train_step
from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from ddw_tpu.train.lm_step import (
    init_lm_state,
    lm_loss,
    make_lm_eval_step,
    make_lm_train_step,
)
from ddw_tpu.train.step import TrainState

VOCAB = 32  # divisible by the model axis: vocab-sharded embed/head in the TP test


def tiny_lm(seq_axis=None, dropout=0.0):
    return TransformerLM(vocab_size=VOCAB, max_len=128, hidden=32, depth=2,
                         num_heads=2, mlp_dim=64, dropout=dropout,
                         dtype=jnp.float32, seq_axis=seq_axis)


def make_batch(rng, batch, seq):
    tokens = rng.randint(0, VOCAB, size=(batch, seq + 1)).astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]


def test_forward_shape_and_causality():
    model = tiny_lm()
    inputs = np.arange(16, dtype=np.int32).reshape(1, 16) % VOCAB
    params = model.init({"params": jax.random.PRNGKey(0)}, inputs)["params"]
    logits = model.apply({"params": params}, inputs)
    assert logits.shape == (1, 16, VOCAB)
    # causality: perturbing token t must not change logits at positions < t
    t = 9
    perturbed = inputs.copy()
    perturbed[0, t] = (perturbed[0, t] + 1) % VOCAB
    logits2 = model.apply({"params": params}, perturbed)
    np.testing.assert_allclose(logits[0, :t], logits2[0, :t], atol=1e-5)
    assert not np.allclose(logits[0, t:], logits2[0, t:], atol=1e-5)


def test_sp_forward_matches_single_device():
    """Ring-attention LM under shard_map(seq=4) == full-attention LM, same params."""
    n = 4
    mesh = make_mesh(MeshSpec(((SEQ_AXIS, n),)), devices=jax.devices()[:n])
    full = tiny_lm()
    sp = tiny_lm(seq_axis=SEQ_AXIS)
    rng = np.random.RandomState(0)
    inputs, _ = make_batch(rng, batch=2, seq=32)
    params = full.init({"params": jax.random.PRNGKey(1)}, inputs)["params"]

    ref = full.apply({"params": params}, inputs)
    sp_fwd = jax.jit(shard_map(
        lambda p, x: sp.apply({"params": p}, x),
        mesh=mesh, in_specs=(P(), P(None, SEQ_AXIS)),
        out_specs=P(None, SEQ_AXIS, None), check_vma=False))
    out = sp_fwd(params, inputs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_dpxsp_train_step_matches_pure_dp():
    """One train step on a (data=2, seq=4) mesh == the same step on (data=2)."""
    devs = jax.devices()
    mesh_sp = make_mesh(MeshSpec(((DATA_AXIS, 2), (SEQ_AXIS, 4))), devices=devs[:8])
    mesh_dp = make_mesh(MeshSpec(((DATA_AXIS, 2),)), devices=devs[:2])
    # SGD: updates are linear in the gradients, so the tiny numeric differences
    # between the flash (DP) and ring (SP) attention paths stay tiny in params
    # (Adam's sign-like normalization would amplify them for near-zero grads).
    tx = optax.sgd(1e-1)
    rng = np.random.RandomState(1)
    inputs, targets = make_batch(rng, batch=4, seq=32)

    model_sp = tiny_lm(seq_axis=SEQ_AXIS)
    state_sp = init_lm_state(model_sp, tx, jax.random.PRNGKey(2))
    step_sp = make_lm_train_step(model_sp, tx, mesh_sp, seq_axis=SEQ_AXIS,
                                 donate=False)

    model_dp = tiny_lm()
    state_dp = init_lm_state(model_dp, tx, jax.random.PRNGKey(2))
    step_dp = make_lm_train_step(model_dp, tx, mesh_dp, seq_axis=None,
                                 donate=False)

    new_sp, m_sp = step_sp(state_sp, inputs, targets, jax.random.PRNGKey(3))
    new_dp, m_dp = step_dp(state_dp, inputs, targets, jax.random.PRNGKey(3))
    assert abs(float(m_sp["loss"]) - float(m_dp["loss"])) < 1e-4
    flat_sp = jax.tree.leaves(new_sp.params)
    flat_dp = jax.tree.leaves(new_dp.params)
    for a, b in zip(flat_sp, flat_dp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.slow  # tier-1 budget (PR 16): the LM learn pin keeps its
#                    tier-1 rep in test_lm_trainer.py::test_fit_learns_dp
#                    (same model through the fit loop); this model-level
#                    soak rides tier-2
def test_lm_learns_fixed_sequence():
    """A few steps of the DPxSP step memorize a constant next-token pattern."""
    n = 4
    mesh = make_mesh(MeshSpec(((DATA_AXIS, 2), (SEQ_AXIS, 2))),
                     devices=jax.devices()[:n])
    model = tiny_lm(seq_axis=SEQ_AXIS)
    tx = optax.adam(5e-3)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0))
    step = make_lm_train_step(model, tx, mesh, seq_axis=SEQ_AXIS)
    eval_step = make_lm_eval_step(model, mesh, seq_axis=SEQ_AXIS)

    seq = np.tile(np.arange(16, dtype=np.int32) % VOCAB, (4, 1))
    inputs, targets = seq[:, :-1][:, :12], seq[:, 1:][:, :12]
    first = None
    for i in range(30):
        state, metrics = step(state, inputs, targets, jax.random.PRNGKey(i))
        if first is None:
            first = float(metrics["loss"])
    final = eval_step(state, inputs, targets)
    assert float(final["loss"]) < first / 3
    assert float(final["accuracy"]) > 0.9


def test_sp_global_seq_exceeding_max_len_raises():
    """dynamic_slice would silently clamp trailing shards' position offsets —
    the model must reject global seq > max_len at trace time instead."""
    n = 4
    mesh = make_mesh(MeshSpec(((SEQ_AXIS, n),)), devices=jax.devices()[:n])
    sp = tiny_lm(seq_axis=SEQ_AXIS)  # max_len=128
    inputs = np.zeros((1, 256), np.int32)  # global 256 > 128
    params = tiny_lm().init({"params": jax.random.PRNGKey(0)},
                            inputs[:, :8])["params"]
    fwd = jax.jit(shard_map(
        lambda p, x: sp.apply({"params": p}, x),
        mesh=mesh, in_specs=(P(), P(None, SEQ_AXIS)),
        out_specs=P(None, SEQ_AXIS, None), check_vma=False))
    with pytest.raises(ValueError, match="max_len"):
        fwd(params, inputs)


def test_lm_seq_axis_mismatch_raises():
    mesh = make_mesh(MeshSpec(((DATA_AXIS, 2),)), devices=jax.devices()[:2])
    model = tiny_lm(seq_axis=SEQ_AXIS)
    with pytest.raises(ValueError, match="seq_axis"):
        make_lm_train_step(model, optax.adam(1e-3), mesh, seq_axis=None)


def test_lm_tensor_parallel_gspmd_step():
    """LM under the GSPMD TP path: params shard per LM_TP_RULES, loss finite."""
    mesh = make_mesh(MeshSpec(((DATA_AXIS, 2), (MODEL_AXIS, 2))),
                     devices=jax.devices()[:4])
    model = tiny_lm()
    tx = optax.adam(1e-3)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0))
    step = make_sharded_train_step(model, tx, mesh, LM_TP_RULES)
    state = step.place_state(state)
    emb = state.params["tok_embed"]["embedding"]
    assert emb.sharding.spec == P(MODEL_AXIS, None), emb.sharding
    rng = np.random.RandomState(2)
    inputs, targets = make_batch(rng, batch=4, seq=16)
    inputs = jax.device_put(inputs, step.batch_sharding)
    targets = jax.device_put(targets, step.batch_sharding)
    state, metrics = step(state, inputs, targets, jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))


def test_decode_path_matches_full_forward():
    """KV-cached one-token-at-a-time logits == full-sequence forward logits."""
    from ddw_tpu.models.lm import generate  # noqa: F401 (import sanity)
    import jax.numpy as jnp
    from jax import lax

    model = tiny_lm()
    rng = np.random.RandomState(4)
    tokens = rng.randint(0, VOCAB, size=(2, 12)).astype(np.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]
    full_logits = model.apply({"params": params}, tokens)

    from ddw_tpu.models.lm import init_cache

    dm = model.clone(decode=True)
    cache = init_cache(dm, batch=2)

    def one(cache, tok):
        logits, vars_ = dm.apply({"params": params, "cache": cache},
                                 tok[:, None], mutable=["cache"])
        return vars_["cache"], logits[:, 0]

    _, step_logits = lax.scan(one, cache, jnp.asarray(tokens).T)
    step_logits = jnp.transpose(step_logits, (1, 0, 2))  # [B, S, V]
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(full_logits), atol=2e-4)


@pytest.mark.slow  # ~12s; learn pin stays tier-1 in
#                    test_lm_trainer.py::test_fit_learns_dp,
#                    generate identity in test_decode_path_matches_full_forward
def test_generate_continues_memorized_pattern():
    """Train on the arange successor pattern, then greedy-generate continues it."""
    from ddw_tpu.models.lm import generate

    mesh = make_mesh(MeshSpec(((DATA_AXIS, 2),)), devices=jax.devices()[:2])
    model = tiny_lm()
    tx = optax.adam(5e-3)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0))
    step = make_lm_train_step(model, tx, mesh, seq_axis=None)
    seq = np.tile(np.arange(24, dtype=np.int32) % VOCAB, (4, 1))
    inputs, targets = seq[:, :-1], seq[:, 1:]
    for i in range(60):
        state, metrics = step(state, inputs, targets, jax.random.PRNGKey(i))
    assert float(metrics["accuracy"]) > 0.95

    prompt = np.arange(6, dtype=np.int32)[None] % VOCAB   # 0..5
    cont = np.asarray(generate(model, state.params, prompt, num_steps=8))
    expected = (np.arange(6, 14) % VOCAB).astype(np.int32)
    np.testing.assert_array_equal(cont[0], expected)


def test_generate_rejects_overflow_and_sampling_without_rng():
    from ddw_tpu.models.lm import generate

    model = tiny_lm()  # max_len=128
    params = model.init({"params": jax.random.PRNGKey(0)},
                        np.zeros((1, 4), np.int32))["params"]
    with pytest.raises(ValueError, match="exceeds"):
        generate(model, params, np.zeros((1, 100), np.int32), num_steps=60)
    with pytest.raises(ValueError, match="requires rng"):
        generate(model, params, np.zeros((1, 4), np.int32), num_steps=2,
                 temperature=0.8)


def test_decode_work_scales_with_position():
    """Tiled decode attention must skip unfilled cache tiles: the per-call tile
    count (cache['tiles_computed'] delta, summed over layers) grows with the
    filled position instead of always paying O(max_len)."""
    model = TransformerLM(vocab_size=16, max_len=1024, hidden=16, depth=2,
                          num_heads=2, mlp_dim=32, dtype=jnp.float32,
                          decode=True)
    # tile=256, max_len=1024 -> 4 tiles per layer available
    rng = np.random.RandomState(0)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 1), jnp.int32))["params"]
    from ddw_tpu.models.lm import init_cache

    cache = init_cache(model, 1)

    def step_at(cache):
        tok = jnp.asarray(rng.randint(0, 16, size=(1, 1)), jnp.int32)
        _, vars_ = model.apply({"params": params, "cache": cache}, tok,
                               mutable=["cache"])
        return vars_["cache"]

    def total_tiles(cache):
        import jax as _jax
        flat = _jax.tree_util.tree_flatten_with_path(cache)[0]
        return sum(int(v) for k, v in flat if "tiles_computed" in str(k))

    c = cache
    before = total_tiles(c)
    c = step_at(c)                      # pos 0: 1 active tile per layer
    early = total_tiles(c) - before
    assert early == 2                   # depth=2 layers x 1 tile

    # fast-forward the index to tile 3 (simulate 800 generated tokens)
    c = jax.tree_util.tree_map_with_path(
        lambda k, v: jnp.asarray(800, jnp.int32)
        if "cache_index" in str(k) or "pos_index" in str(k) else v, c)
    before = total_tiles(c)
    c = step_at(c)                      # pos 800 -> tiles 0..3 active
    late = total_tiles(c) - before
    assert late == 8                    # depth=2 layers x 4 tiles
    assert late > early


def test_decode_overflow_poisons_output():
    """Driving the decode model past max_len must fail loudly (NaN logits),
    not silently clamp-overwrite the cache."""
    model = TransformerLM(vocab_size=16, max_len=8, hidden=16, depth=1,
                          num_heads=2, mlp_dim=32, dtype=jnp.float32,
                          decode=True)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 1), jnp.int32))["params"]
    from ddw_tpu.models.lm import init_cache

    cache = init_cache(model, 1)
    tok = jnp.zeros((1, 1), jnp.int32)
    for i in range(8):
        logits, vars_ = model.apply({"params": params, "cache": cache}, tok,
                                    mutable=["cache"])
        cache = vars_["cache"]
        assert np.isfinite(np.asarray(logits)).all(), f"step {i} not finite"
    logits, _ = model.apply({"params": params, "cache": cache}, tok,
                            mutable=["cache"])
    assert np.isnan(np.asarray(logits)).all()


@pytest.mark.slow  # ~12s; filter-edge pins (top_k=1==greedy etc.) move to
# the slow tier; seeded/greedy sampling identity keeps tier-1 reps in
# test_lanes.py::test_batch_matches_direct_greedy_and_seeded and
# tests/test_paged_kv.py's sampled+greedy neighbor test
def test_generate_top_k_top_p():
    """top_k=1 (or a vanishing nucleus) at ANY temperature must reproduce the
    greedy continuation; top_k/top_p compose with sampling and error-check."""
    from ddw_tpu.models.lm import generate

    model = tiny_lm()
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    prompt = np.arange(8, dtype=np.int32)[None] % model.vocab_size
    rng = jax.random.PRNGKey(7)

    greedy = np.asarray(generate(model, params, prompt, num_steps=12))
    k1 = np.asarray(generate(model, params, prompt, num_steps=12, rng=rng,
                             temperature=5.0, top_k=1))
    np.testing.assert_array_equal(k1, greedy)
    p_tiny = np.asarray(generate(model, params, prompt, num_steps=12, rng=rng,
                                 temperature=5.0, top_p=1e-9))
    np.testing.assert_array_equal(p_tiny, greedy)

    # full nucleus == plain categorical at the same key
    plain = np.asarray(generate(model, params, prompt, num_steps=12, rng=rng,
                                temperature=1.0))
    p_full = np.asarray(generate(model, params, prompt, num_steps=12, rng=rng,
                                 temperature=1.0, top_p=1.0))
    np.testing.assert_array_equal(p_full, plain)

    # composed sampling stays in-vocab and actually varies with the key
    s1 = np.asarray(generate(model, params, prompt, num_steps=24, rng=rng,
                             temperature=2.0, top_k=8, top_p=0.9))
    s2 = np.asarray(generate(model, params, prompt, num_steps=24,
                             rng=jax.random.PRNGKey(8),
                             temperature=2.0, top_k=8, top_p=0.9))
    assert s1.min() >= 0 and s1.max() < model.vocab_size
    assert (s1 != s2).any()

    with pytest.raises(ValueError, match="top_p must be in"):
        generate(model, params, prompt, 4, rng=rng, temperature=1.0, top_p=1.5)
    with pytest.raises(ValueError, match="top_k must be"):
        generate(model, params, prompt, 4, rng=rng, temperature=1.0, top_k=-3)
    with pytest.raises(ValueError, match="require temperature"):
        generate(model, params, prompt, 4, top_k=5)


@pytest.mark.slow  # tier-1 budget (PR 16): grad-accum equivalence keeps
#                    tier-1 reps in test_train_step.py (vision twin),
#                    test_chain's grad-accum chain arm and test_zero's
#                    accum-vs-single-shot pin; the LM variant rides tier-2
def test_lm_grad_accum_equivalence():
    """grad_accum_steps=2 == one full-batch LM step (dropout off, SGD so the
    update is linear in the gradients)."""
    mesh = make_mesh(MeshSpec(((DATA_AXIS, 2),)), devices=jax.devices()[:2])
    model = tiny_lm()
    tx = optax.sgd(1e-1)
    state0 = init_lm_state(model, tx, jax.random.PRNGKey(2))
    step1 = make_lm_train_step(model, tx, mesh, seq_axis=None, donate=False)
    step2 = make_lm_train_step(model, tx, mesh, seq_axis=None, donate=False,
                               grad_accum_steps=2)
    rng = np.random.RandomState(4)
    inputs, targets = make_batch(rng, batch=8, seq=32)
    s1, m1 = step1(state0, inputs, targets, jax.random.PRNGKey(5))
    s2, m2 = step2(state0, inputs, targets, jax.random.PRNGKey(5))
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
