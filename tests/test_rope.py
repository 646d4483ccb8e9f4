"""RoPE (ddw_tpu.ops.rope): the relative-position property, and the LM
family's three execution modes (full, SP ring, KV-cached decode) agreeing
under pos_encoding='rope'."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddw_tpu.models.lm import TransformerLM, generate
from ddw_tpu.ops.rope import apply_rope


def test_rotation_preserves_norm_and_zero_position_is_identity():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 3, 8, 16).astype(np.float32))
    pos = jnp.arange(8)
    y = apply_rope(x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    y0 = apply_rope(x, jnp.zeros(8, jnp.int32))
    np.testing.assert_allclose(np.asarray(y0), np.asarray(x), atol=1e-6)


def test_scores_depend_on_relative_position():
    """<rope(q, p+i), rope(k, p+j)> is invariant in p — the defining RoPE
    property that makes cached/ring K position-free."""
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 1, 4, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 1, 4, 16).astype(np.float32))

    def scores(base):
        pos = base + jnp.arange(4)
        qr, kr = apply_rope(q, pos), apply_rope(k, pos)
        return np.asarray(jnp.einsum("bhqd,bhkd->bhqk", qr, kr))

    np.testing.assert_allclose(scores(0), scores(1000), rtol=1e-4, atol=1e-4)
    # and rotation by different positions actually changes the scores
    assert not np.allclose(
        scores(0),
        np.asarray(jnp.einsum("bhqd,bhkd->bhqk", q, k)), atol=1e-3)


def test_seq_axis_layouts_agree():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 6, 4, 8).astype(np.float32))  # [B,S,H,hd]
    pos = jnp.arange(6) + 3
    a = apply_rope(x, pos, seq_axis=1)
    b = apply_rope(x.transpose(0, 2, 1, 3), pos).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_apply_rope_validation():
    x = jnp.zeros((1, 4, 2, 8))
    with pytest.raises(ValueError, match="positions"):
        apply_rope(x, jnp.arange(3), seq_axis=1)
    with pytest.raises(ValueError, match="even head_dim"):
        apply_rope(jnp.zeros((1, 4, 2, 7)), jnp.arange(4), seq_axis=1)
    with pytest.raises(ValueError, match="seq_axis cannot"):
        apply_rope(x, jnp.arange(8), seq_axis=-1)


def _rope_lm(depth=2, **kw):
    return TransformerLM(vocab_size=32, max_len=64, hidden=16, depth=depth,
                         num_heads=2, dtype=jnp.float32, mlp_dim=32,
                         pos_encoding="rope", **kw)


def test_rope_lm_has_no_pos_table_and_validates():
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 32, (2, 8)))
    params = _rope_lm().init({"params": jax.random.PRNGKey(0)}, toks)["params"]
    assert "pos_embed" not in params
    with pytest.raises(ValueError, match="unknown pos_encoding"):
        TransformerLM(vocab_size=8, hidden=16, num_heads=2,
                      pos_encoding="alibi").init(
            {"params": jax.random.PRNGKey(0)}, toks)
    with pytest.raises(ValueError, match="even head_dim"):
        TransformerLM(vocab_size=8, hidden=6, num_heads=2,
                      pos_encoding="rope").init(
            {"params": jax.random.PRNGKey(0)}, toks)


def test_rope_position_sensitivity():
    """The model distinguishes token order without any pos table."""
    rng = np.random.RandomState(3)
    model = _rope_lm()
    toks = jnp.asarray(rng.randint(0, 32, (1, 8)))
    params = model.init({"params": jax.random.PRNGKey(0)}, toks)["params"]
    swapped = np.asarray(toks).copy()
    swapped[0, [2, 5]] = swapped[0, [5, 2]]
    out1 = model.apply({"params": params}, toks)
    out2 = model.apply({"params": params}, jnp.asarray(swapped))
    assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]),
                           atol=1e-5)


@pytest.mark.slow   # tier-1 budget (PR 16): rope math keeps the unit pins
#                     above tier-1 and decode-vs-full identity keeps
#                     test_lm.py::test_decode_path_matches_full_forward
#                     (learned-pos twin); this rope decode sweep rides
#                     tier-2 with the sp-ring / pp composition arms
def test_rope_decode_matches_full_forward():
    """Prefill + per-token decode through the rotated KV cache reproduces the
    full causal forward (the rope analog of
    test_lm.py::test_decode_path_matches_full_forward)."""
    rng = np.random.RandomState(4)
    model = _rope_lm()
    toks = jnp.asarray(rng.randint(0, 32, (2, 10)))
    params = model.init({"params": jax.random.PRNGKey(0)}, toks)["params"]
    full = model.apply({"params": params}, toks)

    from ddw_tpu.models.lm import init_cache

    dm = model.clone(decode=True)
    cache = init_cache(dm, 2)
    logits_steps = []
    for t in range(10):
        lg, vars_ = dm.apply({"params": params, "cache": cache},
                             toks[:, t:t + 1], mutable=["cache"])
        cache = vars_["cache"]
        logits_steps.append(lg[:, 0])
    stepwise = jnp.stack(logits_steps, axis=1)
    np.testing.assert_allclose(np.asarray(stepwise), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow   # 8-device ring-attention equivalence (~14 s);
#                     tier-2 keeps it
def test_rope_sp_ring_matches_single_device():
    """Ring attention with per-shard pre-rotated K equals the full forward
    (K needs no position plumbing through the ring)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.RandomState(5)
    toks = jnp.asarray(rng.randint(0, 32, (2, 32)))
    base = _rope_lm()
    params = base.init({"params": jax.random.PRNGKey(0)}, toks)["params"]
    full = base.apply({"params": params}, toks)

    sp_model = _rope_lm(seq_axis="seq")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("seq",))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(None, "seq")), out_specs=P(None, "seq", None),
        check_vma=False)
    def sharded_fwd(p, t):
        return sp_model.apply({"params": p}, t)

    out = sharded_fwd(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # ~8s composition drill like the sp-ring one above;
# tier-1 reps: rope units here + test_pipeline's gpipe equivalence arm
def test_rope_pp_step_matches_single_device():
    """The pipeline step threads RoPE positions through its stages: one
    4-stage PP step == one plain step, loss and params (the rope analog of
    test_pipeline.py::test_pp_train_step_matches_single_device)."""
    import optax

    from ddw_tpu.parallel.pipeline import (init_pp_state, lm_params_from_pp,
                                           make_pp_lm_train_step)
    from ddw_tpu.runtime.mesh import DATA_AXIS, MeshSpec, make_mesh
    from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step

    n = 4
    mesh_pp = make_mesh(MeshSpec((("pipe", n),)), devices=jax.devices()[:n])
    mesh_1 = make_mesh(MeshSpec(((DATA_AXIS, 1),)), devices=jax.devices()[:1])
    model = _rope_lm(depth=4)
    tx = optax.sgd(1e-1)
    rng = np.random.RandomState(7)
    toks = jnp.asarray(rng.randint(0, 32, (8, 17)))
    inputs, targets = toks[:, :-1], toks[:, 1:]

    ref_state = init_lm_state(model, tx, jax.random.PRNGKey(1))
    ref_step = make_lm_train_step(model, tx, mesh_1, DATA_AXIS, seq_axis=None,
                                  donate=False)
    ref_new, ref_m = ref_step(ref_state, inputs, targets, jax.random.PRNGKey(2))

    pp_state = init_pp_state(model, tx, mesh_pp, jax.random.PRNGKey(1))
    step = make_pp_lm_train_step(model, tx, mesh_pp, num_microbatches=4,
                                 donate=False)
    pp_state = step.place_state(pp_state)
    pp_new, pp_m = step(pp_state, inputs, targets)
    assert abs(float(pp_m["loss"]) - float(ref_m["loss"])) < 1e-5
    got = lm_params_from_pp(jax.device_get(pp_new.params), n, model.depth)
    assert "pos_embed" not in got
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        got, jax.device_get(ref_new.params))


def test_rope_generate_runs():
    model = _rope_lm()
    toks = jnp.asarray(np.random.RandomState(6).randint(0, 32, (2, 4)))
    params = model.init({"params": jax.random.PRNGKey(0)}, toks)["params"]
    out = generate(model, params, toks, num_steps=5)
    assert out.shape == (2, 5)
    assert not np.any(np.isnan(np.asarray(out)))
