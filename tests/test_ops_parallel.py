"""Pallas flash attention + ring attention + TP sharding tests (8-dev CPU mesh;
pallas runs in interpret mode off-TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddw_tpu.ops.flash_attention import flash_attention, mha_reference
from ddw_tpu.parallel.ring_attention import ring_attention
from ddw_tpu.parallel.sharding import (
    VIT_TP_RULES,
    make_sharded_train_step,
    shardings_for_params,
)
from ddw_tpu.runtime.mesh import make_mesh, MeshSpec


def _qkv(b=2, h=2, s=256, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d).astype(np.float32), dtype=dtype)
    return mk(), mk(), mk()


def test_flash_matches_reference():
    q, k, v = _qkv()
    out = flash_attention(q, k, v)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_causal():
    q, k, v = _qkv(s=256)
    out = flash_attention(q, k, v, True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # causality: output at position 0 must not depend on later keys
    v2 = v.at[:, :, 128:, :].set(0.0)
    out2 = flash_attention(q, k, v2, True)
    np.testing.assert_allclose(np.asarray(out[:, :, :128]), np.asarray(out2[:, :, :128]),
                               rtol=1e-5, atol=1e-5)


def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    ref = mha_reference(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_gradients():
    q, k, v = _qkv(b=1, h=1, s=128, d=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_flash_offsets():
    """q_offset/k_offset shift the causal mask to global positions (ring case)."""
    q, k, v = _qkv(s=128)
    # k block globally BEFORE q block: fully visible
    out_past = flash_attention(q, k, v, True, 128, 0)
    ref_full = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out_past), np.asarray(ref_full),
                               rtol=2e-5, atol=2e-5)
    # k block globally AFTER q block: fully masked -> uniform-ish? No: all -inf
    # rows normalize over zero mass; guard returns zeros
    out_future = flash_attention(q, k, v, True, 0, 128)
    assert np.isfinite(np.asarray(out_future)).all()


def test_flash_misaligned_offset_masked_rows_zero():
    """Rows fully masked by a NON-block-aligned offset must emit zeros.

    With k_offset=64 and block_k=128, query rows 0-63 have every key masked but
    the k block kb=0 still passes the block-level visibility check — the kernel
    must not let exp(s - m_new) == 1 give masked keys weight (regression test)."""
    q2, k2, v2 = _qkv(s=256, seed=3)
    out = flash_attention(q2[:, :, :128, :], k2, v2, True, 0, 64)
    arr = np.asarray(out)
    # rows 0-63: zero visible keys -> zeros
    np.testing.assert_array_equal(arr[:, :, :64, :], 0.0)
    # rows 64-127: match reference on the visible prefix
    ref = np.asarray(mha_reference(q2[:, :, :128, :], k2, v2, causal=True,
                                   q_offset=0, k_offset=64))
    np.testing.assert_allclose(arr[:, :, 64:, :], ref[:, :, 64:, :],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    n_seq = 4
    mesh = make_mesh(MeshSpec((("seq", n_seq),)), devices=jax.devices()[:n_seq])
    b, h, s, d = 2, 2, 64 * n_seq, 32
    rng = np.random.RandomState(1)
    q = rng.randn(b, h, s, d).astype(np.float32)
    k = rng.randn(b, h, s, d).astype(np.float32)
    v = rng.randn(b, h, s, d).astype(np.float32)

    def f(q, k, v):
        return ring_attention(q, k, v, "seq", causal=causal)

    smapped = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(None, None, "seq", None),) * 3,
        out_specs=P(None, None, "seq", None), check_vma=False))
    out = smapped(q, k, v)
    ref = mha_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_tp_rules_spec_resolution():
    from ddw_tpu.models.registry import build_model
    from ddw_tpu.utils.config import ModelCfg

    model = build_model(ModelCfg(name="vit", num_classes=5, dtype="float32"))
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 32, 32, 3)), train=False)["params"]
    mesh = make_mesh(MeshSpec((("data", 4), ("model", 2))))
    sh = shardings_for_params(params, mesh, VIT_TP_RULES)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    by_key = {"/".join(str(getattr(p, "key", p)) for p in path): s for path, s in flat}
    mlp1 = next(v for k, v in by_key.items() if "mlp/fc1/kernel" in k)
    assert mlp1.spec == P(None, "model")
    attn_q = next(v for k, v in by_key.items() if "attn/query/kernel" in k)
    assert attn_q.spec == P(None, "model", None)
    patch = next(v for k, v in by_key.items() if "patch_embed/kernel" in k)
    assert patch.spec == P()


@pytest.mark.slow  # tier-1 budget (PR 18): TP-in-training keeps tier-1 reps
                   # in test_tp_rules_spec_resolution (rules unit) +
                   # test_fsdp.py::test_fsdp_tp_learns_on_2x4 (composition).
def test_tp_train_step_vit():
    """dp=4 x tp=2 GSPMD train step on ViT: runs, loss drops, params shard."""
    import optax

    from ddw_tpu.models.registry import build_model
    from ddw_tpu.train.step import TrainState
    from ddw_tpu.utils.config import ModelCfg

    mesh = make_mesh(MeshSpec((("data", 4), ("model", 2))))
    model = build_model(ModelCfg(name="vit", num_classes=5, dropout=0.0, dtype="float32"))
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 32, 32, 3)), train=False)["params"]
    tx = optax.adam(1e-3)
    state = TrainState(params, {}, tx.init(params), jnp.zeros((), jnp.int32))
    step = make_sharded_train_step(model, tx, mesh, VIT_TP_RULES)
    state = step.place_state(state)

    # param actually sharded over model axis
    fc1 = state.params["backbone_block0"]["mlp"]["fc1"]["kernel"]
    assert fc1.sharding.spec == P(None, "model")

    rng = np.random.RandomState(0)
    images = jax.device_put(rng.randn(16, 32, 32, 3).astype(np.float32),
                            step.batch_sharding)
    labels = jax.device_put(rng.randint(0, 5, (16,)).astype(np.int32),
                            step.batch_sharding)
    losses = []
    for _ in range(8):
        state, metrics = step(state, images, labels, jax.random.PRNGKey(1))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    # adam moments sharded like their params (rules matched on path suffix)
    mu_fc1 = state.opt_state[0].mu["backbone_block0"]["mlp"]["fc1"]["kernel"]
    assert mu_fc1.sharding.spec == P(None, "model")


def test_flash_gradients_noncausal_and_offsets():
    """Pallas backward == reference backward without causal masking and with
    ring-style global offsets (the cross-shard case)."""
    q, k, v = _qkv(b=2, h=2, s=256, d=32, seed=5)

    # q_offset > k_offset keeps every q row partially visible; rows with ZERO
    # visible keys diverge from the reference by design (its all-masked softmax
    # degenerates to uniform) — that case is pinned by
    # test_flash_gradients_fully_masked_rows_zero instead.
    for kwargs in ({"causal": False}, {"causal": True, "q_offset": 256},
                   {"causal": True, "q_offset": 64, "k_offset": 0}):
        def lf(q, k, v):
            return jnp.sum(flash_attention(q, k, v, kwargs.get("causal", False),
                                           kwargs.get("q_offset", 0),
                                           kwargs.get("k_offset", 0)) ** 2)

        def lr(q, k, v):
            return jnp.sum(mha_reference(q, k, v, kwargs.get("causal", False),
                                         kwargs.get("q_offset", 0),
                                         kwargs.get("k_offset", 0)) ** 2)

        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4, err_msg=str(kwargs))


def test_flash_gradients_bf16_multiblock():
    """bf16 grads across multiple q/k blocks stay close to the f32 reference."""
    q, k, v = _qkv(b=1, h=2, s=384, d=32, seed=7)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True).astype(jnp.float32) ** 2)

    def lr(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2))(qb, kb, vb)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a).astype(np.float32),
                                   np.asarray(b), rtol=0.1, atol=0.1)


def test_flash_gradients_fully_masked_rows_zero():
    """Rows with zero visible keys must get zero dQ (and contribute nothing to
    dK/dV), not NaNs from the masked-softmax residuals."""
    q, k, v = _qkv(s=128, seed=9)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 0, 64) ** 2)

    gq, gk, gv = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    assert np.isfinite(np.asarray(gq)).all()
    assert np.isfinite(np.asarray(gk)).all()
    assert np.isfinite(np.asarray(gv)).all()
    np.testing.assert_array_equal(np.asarray(gq)[:, :, :64, :], 0.0)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients_match_full(causal):
    """SP ring backward (with per-hop remat) == full-attention backward."""
    n_seq = 4
    mesh = make_mesh(MeshSpec((("seq", n_seq),)), devices=jax.devices()[:n_seq])
    b, h, s, d = 1, 2, 32 * n_seq, 16
    rng = np.random.RandomState(2)
    q = rng.randn(b, h, s, d).astype(np.float32)
    k = rng.randn(b, h, s, d).astype(np.float32)
    v = rng.randn(b, h, s, d).astype(np.float32)

    def ring_loss(q, k, v):
        out = shard_map(
            lambda q, k, v: ring_attention(q, k, v, "seq", causal=causal),
            mesh=mesh, in_specs=(P(None, None, "seq", None),) * 3,
            out_specs=P(None, None, "seq", None), check_vma=False)(q, k, v)
        return jnp.sum(out ** 2)

    def full_loss(q, k, v):
        return jnp.sum(mha_reference(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=causal) ** 2)

    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


def test_flash_lse_matches_logsumexp():
    """flash_attention_lse's second output == logsumexp of the scaled scores."""
    from ddw_tpu.ops.flash_attention import flash_attention_lse

    q, k, v = _qkv(b=1, h=2, s=256, d=32, seed=4)
    out, lse = flash_attention_lse(q, k, v)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    ref_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-5, atol=1e-5)


def test_flash_lse_split_combine_gradients():
    """Splitting keys in two flash_attention_lse calls and softmax-combining
    them must match full attention in value AND gradients — the exact contract
    ring attention relies on per hop (exercises the lse cotangent path)."""
    from ddw_tpu.ops.flash_attention import flash_attention_lse
    from ddw_tpu.parallel.ring_attention import _combine

    q, k, v = _qkv(b=1, h=1, s=128, d=32, seed=5)
    k2, v2 = jnp.concatenate([k, k], 2), jnp.concatenate([v, v + 1.0], 2)

    def split_loss(q, k2, v2):
        o1, l1 = flash_attention_lse(q, k2[:, :, :128], v2[:, :, :128])
        o2, l2 = flash_attention_lse(q, k2[:, :, 128:], v2[:, :, 128:])
        out, _ = _combine(o1.astype(jnp.float32), l1,
                          o2.astype(jnp.float32), l2)
        return jnp.sum(out ** 2)

    def full_loss(q, k2, v2):
        return jnp.sum(mha_reference(q, k2, v2) ** 2)

    gs = jax.grad(split_loss, argnums=(0, 1, 2))(q, k2, v2)
    gf = jax.grad(full_loss, argnums=(0, 1, 2))(q, k2, v2)
    np.testing.assert_allclose(split_loss(q, k2, v2), full_loss(q, k2, v2),
                               rtol=1e-4)
    for a, b in zip(gs, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_mha_padded_seq():
    """flash_mha(impl='pallas') pads non-block-multiple lengths (ViT's 196) and
    matches the reference on the unpadded region, fwd and grad."""
    from ddw_tpu.ops.flash_attention import flash_mha

    q, k, v = _qkv(b=1, h=2, s=196, d=48, seed=6)
    out = flash_mha(q, k, v, impl="pallas")
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    gq = jax.grad(lambda q: jnp.sum(flash_mha(q, k, v, impl="pallas") ** 2))(q)
    gr = jax.grad(lambda q: jnp.sum(mha_reference(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(gr),
                               rtol=1e-4, atol=1e-4)


def test_attention_impl_dispatch_equivalence():
    """Every dispatch arm (xla, xla_ckpt, pallas) computes the same attention
    — out, lse, and grads — so the auto rule can never change results."""
    from ddw_tpu.ops.flash_attention import _attn_impl, flash_mha_lse

    q, k, v = _qkv(b=2, h=2, s=160, d=32, seed=8)
    outs = {}
    for impl in ("xla", "xla_ckpt", "pallas"):
        o, lse = flash_mha_lse(q, k, v, causal=True, impl=impl)
        g = jax.grad(lambda q: jnp.sum(
            flash_mha_lse(q, k, v, causal=True, impl=impl)[0] ** 2))(q)
        outs[impl] = (np.asarray(o), np.asarray(lse), np.asarray(g))
    for impl in ("xla_ckpt", "pallas"):
        for a, b, what in zip(outs["xla"], outs[impl], ("out", "lse", "gq")):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                       err_msg=f"{impl} {what}")

    # auto picks by score-matrix footprint
    small = jnp.zeros((1, 1, 128, 16))      # 64 KiB of scores -> plain xla
    big = jnp.zeros((8, 8, 2048, 16))       # 1 GiB -> checkpointed xla
    huge = jnp.zeros((8, 8, 65536, 16))     # 1 TiB -> pallas flash
    assert _attn_impl(small, small, "auto") == "xla"
    assert _attn_impl(big, big, "auto") == "xla_ckpt"
    assert _attn_impl(huge, huge, "auto") == "pallas"
    assert _attn_impl(huge, huge, "xla") == "xla"


def test_vit_flash_mha_matches_flax_attention():
    """FlashMHA (same param layout) must reproduce
    nn.MultiHeadDotProductAttention to tolerance — the ViT swap is a drop-in."""
    import flax.linen as nn

    from ddw_tpu.models.vit import FlashMHA

    b, s, d, heads = 2, 196, 64, 4
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(b, s, d).astype(np.float32))
    mod = FlashMHA(num_heads=heads, dtype=jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x)
    out = mod.apply(params, x)
    ref_mod = nn.MultiHeadDotProductAttention(num_heads=heads, dtype=jnp.float32,
                                              name=None)
    ref = ref_mod.apply(params, x, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_pallas_arm_matches_full():
    """Ring attention with the Pallas kernel forced per hop (the long-context
    configuration) still matches full attention fwd AND grads — the dispatch
    change must not unpin the kernel-in-ring path."""
    from ddw_tpu.parallel.ring_attention import ring_attention
    from jax.sharding import PartitionSpec as P

    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec

    n = 4
    mesh = make_mesh(MeshSpec((("seq", n),)), devices=jax.devices()[:n])
    q, k, v = _qkv(b=1, h=2, s=32 * n, d=32, seed=11)

    def ring_loss(q, k, v):
        fn = shard_map(
            lambda q, k, v: ring_attention(q, k, v, "seq", causal=True,
                                           impl="pallas"),
            mesh=mesh, in_specs=(P(None, None, "seq", None),) * 3,
            out_specs=P(None, None, "seq", None), check_vma=False)
        return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    def full_loss(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True
                                     ).astype(jnp.float32) ** 2)

    np.testing.assert_allclose(float(ring_loss(q, k, v)),
                               float(full_loss(q, k, v)), rtol=1e-4)
    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)
