"""Pallas flash attention + ring attention + TP sharding tests (8-dev CPU mesh;
pallas runs in interpret mode off-TPU)."""

import functools
import importlib

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


fa = importlib.import_module("ddw_tpu.ops.flash_attention")


@pytest.fixture
def dq_home(monkeypatch):
    """``dq_home(home)`` makes the streaming backward keep dQ's sum in
    ``home`` whatever the shapes say (None: the code's own choice) for the
    rest of the test; the jitted entry forgets what it traced before and
    after, or a choice made for the same shapes elsewhere would stand."""
    def force(home):
        if home is not None:
            monkeypatch.setattr(fa, "_dq_home", lambda *shapes: home)
        fa._flash_bwd.clear_cache()

    yield force
    fa._flash_bwd.clear_cache()


def _backward_homes(fn, *args):
    """Where each streaming backward kernel in ``fn``'s jaxpr keeps dQ's sum:
    the ``"hbm"`` form is the one with a fourth output, that sum's buffer."""
    return ["hbm" if len(eqn.outvars) == 4 else "vmem"
            for eqn in _primitives(jax.make_jaxpr(fn)(*args).jaxpr,
                                   "pallas_call")
            if eqn.params["name"] == "flash_dkv"]


def _qkv(b=2, h=2, s=256, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d).astype(np.float32), dtype=dtype)
    return mk(), mk(), mk()


def _ref_attention_lse(q, k, v, causal=False, q_offset=0, k_offset=0):
    """mha_reference's arithmetic (f32 logits, masked at global positions) with
    the logsumexp beside the output — the oracle for out, lse and gradients."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        qpos = q_offset + jnp.arange(q.shape[2])[:, None]
        kpos = k_offset + jnp.arange(k.shape[2])[None, :]
        logits = jnp.where(kpos <= qpos, logits, -1e30)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(logits - lse[..., None]), v)
    return out, lse


# Every kernel configuration the dispatch can choose, and the explicit blocks
# that reach the same code paths at small sizes: sq/sk, head dim, dtype, mask,
# ring offsets, blocks (None = the code's choice), and whether the call goes
# through flash_mha's padding. Each is held to the oracle for out, lse and all
# three gradients, with a cotangent on lse as well as on out.
_KERNEL_CASES = {
    "code_blocks": dict(s=256),
    "code_blocks_causal": dict(s=256, causal=True),
    "code_blocks_bf16": dict(s=256, causal=True, dtype=jnp.bfloat16),
    # heads a 128-lane block: four at d = 32 (two of them padding here), one at
    # d = 128, two at d = 64 with the odd third head's partner padded
    "d32_three_blocks": dict(s=384, d=32, causal=True, block_q=128, block_k=128),
    "d128_one_head_a_block": dict(s=256, d=128, causal=True),
    "three_heads": dict(h=3, s=256, causal=True),
    # one block, larger than the sequence: clamps to it
    "block_larger_than_seq": dict(s=128, causal=True, block_q=512, block_k=1024),
    "block_q_under_block_k": dict(s=512, causal=True, block_q=128, block_k=256),
    "block_q_over_block_k": dict(s=512, causal=True, block_q=256, block_k=128),
    # the LM cells' shape a head: q blocks of 512 over one key block of 1024 in
    # 512-wide sub-blocks — one the diagonal crosses, one wholly above it
    # (skipped), one wholly below (unmasked)
    "cell_blocks": dict(b=1, h=1, s=1024, d=64, causal=True),
    "cell_blocks_bf16": dict(b=1, h=1, s=1024, d=64, causal=True,
                             dtype=jnp.bfloat16),
    "cell_blocks_not_causal": dict(b=1, h=1, s=1024, d=64),
    "two_key_blocks": dict(b=1, h=1, s=2048, d=64, causal=True),
    # ring attention's hops: a shard in the past (fully visible), a misaligned
    # one, and a rectangular one
    "ring_past_hop": dict(s=256, causal=True, q_offset=256),
    "ring_misaligned": dict(s=256, causal=True, q_offset=64),
    "ring_rectangular": dict(sq=128, sk=384, causal=True, q_offset=256),
    # q/k heads wider than v heads (latent attention's 192 and 128, as one and
    # a half lane rows beside one: two heads a lane block, the second read as
    # the aligned window around it with the first's lanes zeroed), and 128
    # beside 64 (two v heads share a lane row); ``dv`` is the v heads' width
    "latent_192_128": dict(b=1, h=4, s=256, d=192, dv=128, causal=True),
    "latent_192_128_bf16": dict(b=1, h=2, s=512, d=192, dv=128, causal=True,
                                dtype=jnp.bfloat16),
    "latent_small_blocks": dict(b=1, h=2, s=384, d=192, dv=128, causal=True,
                                block_q=128, block_k=128),
    "wide_128_64": dict(b=2, h=4, s=256, d=128, dv=64, causal=True),
    "wide_128_64_not_causal_padded": dict(b=1, h=2, s=200, d=128, dv=64,
                                          mha=True),
    # the one-pass backward where dQ is summed over several key blocks. In
    # VMEM (what these shapes' sizes choose): one q block visited once a key
    # block, and three of each without a mask
    "key_blocks_one_q_block": dict(sq=128, sk=384, causal=True, q_offset=256,
                                   block_k=128),
    "three_blocks_not_causal": dict(s=384, block_q=128, block_k=128),
    # ... and through HBM, which the cells' long rows choose and ``home``
    # forces here: each head layout (two heads of 64 a lane block; one of
    # 128; 192 beside 128), several q blocks a key block and one, a padded
    # tail, and offsets off the blocks' grid
    "hbm_d64_two_a_block": dict(s=512, causal=True, block_q=128, block_k=256,
                                home="hbm"),
    "hbm_d128_not_causal": dict(b=1, s=384, d=128, block_q=128, block_k=128,
                                home="hbm"),
    "hbm_latent_192_128": dict(b=1, h=2, s=384, d=192, dv=128, causal=True,
                               block_q=128, block_k=128, home="hbm"),
    "hbm_latent_192_128_bf16": dict(b=1, h=2, s=512, d=192, dv=128,
                                    causal=True, dtype=jnp.bfloat16,
                                    block_q=256, block_k=256, home="hbm"),
    "hbm_one_q_block": dict(sq=128, sk=384, causal=True, q_offset=256,
                            block_k=128, home="hbm"),
    "hbm_one_key_block": dict(s=256, causal=True, home="hbm"),
    "hbm_padded_tail": dict(s=300, causal=True, mha=True, block_q=128,
                            block_k=256, home="hbm"),
    "hbm_misaligned_offsets": dict(s=256, causal=True, q_offset=64,
                                   block_q=128, block_k=128, home="hbm"),
    # flash_mha pads to a block multiple and masks the padded keys (k_valid)
    "padded_vit": dict(s=196, d=48, mha=True),
    "padded_causal": dict(s=160, d=32, causal=True, mha=True),
    "padded_large_block": dict(b=1, h=1, s=1100, d=64, causal=True, mha=True),
    "padded_explicit_blocks": dict(s=300, causal=True, mha=True, block_q=128,
                                   block_k=256),
    # the one-block kernels (sequences under 512): a block that over-runs the
    # array where the length is no multiple of 128 — the interpreter fills the
    # rows past the edge with NaN (pallas's uninitialized_value), so a row the
    # kernels failed to replace by zeros would poison every gradient here
    "short_vit": dict(b=3, s=196, short=True),
    "short_vit_bf16": dict(b=2, s=196, dtype=jnp.bfloat16, short=True),
    "short_causal_100": dict(s=100, causal=True, short=True),
    "short_aligned_256": dict(b=1, s=256, causal=True, short=True),
    "short_384_bf16": dict(b=1, s=384, causal=True, dtype=jnp.bfloat16,
                           short=True),
    "short_d128": dict(b=1, s=196, d=128, short=True),
    "short_d48_three_heads": dict(b=1, h=3, s=100, d=48, causal=True,
                                  short=True),
    "short_q_under_k": dict(b=1, sq=100, sk=300, causal=True, short=True),
    "short_q_over_k": dict(b=1, sq=384, sk=196, short=True),
    "short_d32_four_heads_a_block": dict(b=1, h=5, s=130, d=32, short=True),
}


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_flash_kernels_match_reference(case, dq_home):
    from ddw_tpu.ops.flash_attention import flash_attention_lse, flash_mha_lse

    c = dict(_KERNEL_CASES[case])
    dq_home(c.get("home"))
    dtype = c.get("dtype", jnp.float32)
    causal, q_off, k_off = c.get("causal", False), c.get("q_offset", 0), \
        c.get("k_offset", 0)
    sq, sk = c.get("sq", c.get("s")), c.get("sk", c.get("s"))
    b, h, d = c.get("b", 2), c.get("h", 2), c.get("d", 64)
    rng = np.random.RandomState(len(case))
    q, k, v = (jnp.asarray(rng.randn(b, h, n, w).astype(np.float32), dtype)
               for n, w in ((sq, d), (sk, d), (sk, c.get("dv", d))))
    w_lse = jnp.cos(jnp.arange(sq, dtype=jnp.float32))      # lse cotangent

    def attend(q, k, v):
        if c.get("mha") or c.get("short"):
            return flash_mha_lse(q, k, v, causal, None, c.get("block_q"),
                                 c.get("block_k"),
                                 impl="pallas_short" if c.get("short")
                                 else "pallas")
        return flash_attention_lse(q, k, v, causal, q_off, k_off, None,
                                   c.get("block_q"), c.get("block_k"))

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return (jnp.sum(out.astype(jnp.float32) ** 2)
                    + jnp.sum(lse * w_lse)), (out, lse)
        return f

    ref = functools.partial(_ref_attention_lse, causal=causal, q_offset=q_off,
                            k_offset=k_off)
    (got_g, (out, lse)) = jax.grad(loss(attend), argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    (ref_g, (ref_out, ref_lse)) = jax.grad(loss(ref), argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    if c.get("home"):
        assert _backward_homes(jax.grad(lambda *a: loss(attend)(*a)[0],
                                        argnums=(0, 1, 2)),
                               q, k, v) == [c["home"]]
    tol, gtol = (2e-5, 1e-4) if dtype == jnp.float32 else (3e-2, 0.1)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref_out),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=1e-5 if dtype == jnp.float32 else 1e-2,
                               atol=1e-5 if dtype == jnp.float32 else 1e-2)
    for a, r, what in zip(got_g, ref_g, "qkv"):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(r),
                                   rtol=gtol, atol=gtol, err_msg=f"d{what}")


@pytest.mark.parametrize("s,tier", [(640, "pallas"), (196, "pallas_short"),
                                    (64, "xla")])
def test_flash_mha_seq_major_matches_flash_mha(s, tier):
    """The entry the LM and ViT call ([B,S,H,D] operands) is flash_mha on
    transposed operands, on both kernel forms and on the XLA tiers, forward
    and gradients."""
    from ddw_tpu.ops.flash_attention import (_attn_impl, flash_mha,
                                             flash_mha_seq_major)

    q, k, v = _qkv(b=1, h=2, s=s, d=64, seed=12)
    assert _attn_impl(q, k, "auto") == tier
    t = lambda x: x.transpose(0, 2, 1, 3)                       # noqa: E731

    def loss_seq(q, k, v):
        return jnp.sum(flash_mha_seq_major(t(q), t(k), t(v), causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_mha(q, k, v, causal=True) ** 2)

    np.testing.assert_allclose(
        np.asarray(t(flash_mha_seq_major(t(q), t(k), t(v), causal=True))),
        np.asarray(flash_mha(q, k, v, causal=True)), rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.grad(loss_seq, argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_flash_causal_ignores_later_keys():
    q, k, v = _qkv(s=256)
    out = flash_attention(q, k, v, True)
    # causality: output at position 0 must not depend on later keys
    v2 = v.at[:, :, 128:, :].set(0.0)
    out2 = flash_attention(q, k, v2, True)
    np.testing.assert_allclose(np.asarray(out[:, :, :128]), np.asarray(out2[:, :, :128]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("home", [None, "hbm"])
def test_flash_offsets(home, dq_home):
    """q_offset/k_offset shift the causal mask to global positions (ring case),
    forward and backward, dQ summed over two key blocks in either home."""
    dq_home(home)
    q, k, v = _qkv(s=256)
    attend = functools.partial(flash_attention, causal=True, block_q=128,
                               block_k=128)
    # k block globally BEFORE q block: fully visible
    out_past = attend(q, k, v, q_offset=256, k_offset=0)
    ref_full = mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out_past), np.asarray(ref_full),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(attend(*a, q_offset=256) ** 2),
                   argnums=(0, 1, 2))
    want = jax.grad(lambda *a: jnp.sum(mha_reference(*a) ** 2),
                    argnums=(0, 1, 2))(q, k, v)
    assert _backward_homes(got, q, k, v) == [home or "vmem"]
    for a, r in zip(got(q, k, v), want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)
    # k block globally AFTER q block: fully masked -> uniform-ish? No: all -inf
    # rows normalize over zero mass; guard returns zeros, and no gradient
    out_future = attend(q, k, v, q_offset=0, k_offset=256)
    assert np.isfinite(np.asarray(out_future)).all()
    for g in jax.grad(lambda *a: jnp.sum(attend(*a, k_offset=256) ** 2),
                      argnums=(0, 1, 2))(q, k, v):
        np.testing.assert_array_equal(np.asarray(g), 0.0)


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


@pytest.mark.parametrize("s,block,home", [(128, None, None),
                                          (256, 128, None),
                                          (256, 128, "hbm")])
def test_flash_gradients_fully_masked_rows_zero(s, block, home, dq_home):
    """Rows with zero visible keys must get zero dQ (and contribute nothing to
    dK/dV), not NaNs from the masked-softmax residuals — in one block a side,
    and with dQ summed over two key blocks in either home."""
    dq_home(home)
    q, k, v = _qkv(s=s, seed=9)

    def lf(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 0, 64, None, block,
                                       block) ** 2)

    grad = jax.grad(lf, argnums=(0, 1, 2))
    assert _backward_homes(grad, q, k, v) == [home or "vmem"]
    gq, gk, gv = grad(q, k, v)
    assert np.isfinite(np.asarray(gq)).all()
    assert np.isfinite(np.asarray(gk)).all()
    assert np.isfinite(np.asarray(gv)).all()
    np.testing.assert_array_equal(np.asarray(gq)[:, :, :64, :], 0.0)
    # (row 64 sees one key: its softmax is constant)
    assert np.abs(np.asarray(gq)[:, :, 65:, :]).max(axis=-1).min() > 0.0


@pytest.mark.parametrize("arm", ["auto", "pallas", "pallas_hbm"])
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients_match_full(causal, arm, dq_home):
    """SP ring backward (with per-hop remat) == full-attention backward, on
    the tier the shard's length chooses and with the streaming kernels forced
    a hop (dQ's sum in either home)."""
    impl, _, home = arm.partition("_")
    dq_home(home or None)
    n_seq = 4
    mesh = make_mesh(MeshSpec((("seq", n_seq),)), devices=jax.devices()[:n_seq])
    b, h, s, d = 1, 2, 32 * n_seq, 16
    rng = np.random.RandomState(2)
    q = rng.randn(b, h, s, d).astype(np.float32)
    k = rng.randn(b, h, s, d).astype(np.float32)
    v = rng.randn(b, h, s, d).astype(np.float32)

    def ring_loss(q, k, v):
        out = shard_map(
            lambda q, k, v: ring_attention(q, k, v, "seq", causal=causal,
                                           impl=impl),
            mesh=mesh, in_specs=(P(None, None, "seq", None),) * 3,
            out_specs=P(None, None, "seq", None), check_vma=False)(q, k, v)
        return jnp.sum(out ** 2)

    def full_loss(q, k, v):
        return jnp.sum(mha_reference(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=causal) ** 2)

    if impl == "pallas":
        assert set(_backward_homes(jax.grad(ring_loss, argnums=(0, 1, 2)),
                                   q, k, v)) == {home or "vmem"}
    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("home", [None, "hbm"])
def test_flash_lse_split_combine_gradients(home, dq_home):
    """Splitting keys in two flash_attention_lse calls and softmax-combining
    them must match full attention in value AND gradients — the exact contract
    ring attention relies on per hop (exercises the lse cotangent path)."""
    from ddw_tpu.ops.flash_attention import flash_attention_lse
    from ddw_tpu.parallel.ring_attention import _combine

    dq_home(home)
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


# the attention of the five cells that run the streaming kernels, a chip:
# (Sq = Sk, q/k lanes a head block) and where dQ's sum waits (PERF.md, 3)
_CELL_DQ_HOMES = {
    "gpt2m_train_s1024": ((8, 1024, 16, 64, 64), "vmem"),       # one key block
    "gpt2m_train_dp4": ((8, 1024, 16, 64, 64), "vmem"),
    "nemotron3nano_train_s8192": ((2, 8192, 32, 128, 128), "vmem"),  # 4 MiB
    "xing4_train_s4096": ((1, 4096, 32, 192, 128), "hbm"),      # 6 MiB
    "joyai_flash_train_s8192": ((2, 8192, 32, 192, 128), "hbm"),    # 12 MiB
}


@pytest.mark.parametrize("cell", sorted(_CELL_DQ_HOMES))
def test_dq_home_of_the_cells(cell):
    """The one-pass backward's chooser reads shapes alone and sends each
    cell's attention where PERF.md says: dQ's float32 sum waits in VMEM while
    the kernel stays inside the 16 MiB it gets unasked, else in HBM."""
    (b, s, h, d, dv), home = _CELL_DQ_HOMES[cell]
    bq, bk, _ = fa._resolve_blocks(s, s, None, None, None)
    per, dp, _, _ = fa._head_layout(h, d, dv)
    assert fa._dq_home(s, s, bk, per * dp) == home
    assert (bq, bk) == (512, 1024)


def test_attention_impl_dispatch_equivalence():
    """Every dispatch arm (xla, xla_ckpt, pallas) computes the same attention
    — out, lse, and grads — so the auto rule can never change results."""
    from ddw_tpu.ops.flash_attention import _attn_impl, flash_mha_lse

    q, k, v = _qkv(b=2, h=2, s=160, d=32, seed=8)
    outs = {}
    for impl in ("xla", "xla_ckpt", "pallas", "pallas_short"):
        o, lse = flash_mha_lse(q, k, v, causal=True, impl=impl)
        g = jax.grad(lambda q: jnp.sum(
            flash_mha_lse(q, k, v, causal=True, impl=impl)[0] ** 2))(q)
        outs[impl] = (np.asarray(o), np.asarray(lse), np.asarray(g))
    for impl in ("xla_ckpt", "pallas", "pallas_short"):
        for a, b, what in zip(outs["xla"], outs[impl], ("out", "lse", "gq")):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                       err_msg=f"{impl} {what}")

    # auto reads shapes alone: both sides of _FLASH_MIN_SEQ and more go to the
    # streaming kernels whatever the batch; sequences from _SHORT_MIN_SEQ up
    # that fit one block go to the one-block kernels at the head dims the
    # ladder measured; below that, and at any other head dim, the score
    # footprint picks between the XLA tiers
    from ddw_tpu.ops.flash_attention import (_FLASH_MIN_SEQ, _SHORT_MAX_SEQ,
                                             _SHORT_MIN_SEQ)

    def auto(b, h, s, d=64, sk=None, dtype=jnp.bfloat16):
        return _attn_impl(jax.ShapeDtypeStruct((b, h, s, d), dtype),
                          jax.ShapeDtypeStruct((b, h, sk or s, d), dtype),
                          "auto")

    assert auto(8, 16, 1024) == "pallas"        # the LM cells, batch 8 a chip
    assert auto(8, 16, 1024, dtype=jnp.float32) == "pallas"
    assert auto(1, 1, _FLASH_MIN_SEQ) == "pallas"
    assert auto(128, 12, 196) == "pallas_short"     # vitb16_train_224
    assert auto(1, 2, 196, dtype=jnp.float32) == "pallas_short"
    assert auto(21, 16, 384, d=128) == "pallas_short"
    assert auto(1024, 16, 256) == "pallas_short"    # 4 GiB of scores on XLA
    assert auto(1, 1, _SHORT_MIN_SEQ) == "pallas_short"     # the lower edge
    assert auto(8, 16, _SHORT_MAX_SEQ, sk=256) == "pallas_short"
    assert auto(1, 1, _SHORT_MIN_SEQ - 1) == "xla"
    assert auto(64, 16, 128) == "xla"           # measured: XLA 0.56, 0.74 ms
    assert auto(8, 16, 1024, sk=128) == "xla"   # a short side: no kernel
    assert auto(8, 16, 1024, sk=256) == "xla"   # fits no single block
    assert auto(64, 16, 384, d=32) == "xla_ckpt"    # 576 MiB, head dim unmeasured
    assert auto(1024, 16, 256, d=32) == "pallas"    # 4 GiB of scores do not fit
    # the shapes the CPU suite trains ViT and the LM at stay on XLA, or
    # tier-1 would pay the Pallas interpreter in every model test
    assert auto(8, 4, 4, d=48) == "xla"         # 32 x 32 images, patch 16
    assert auto(8, 2, 16, d=32) == "xla"        # 64 x 64 images
    assert auto(8, 4, 128, d=16) == "xla"       # the smoke LM
    huge = jnp.zeros((1, 1, 128, 16))
    assert _attn_impl(huge, huge, "xla_ckpt") == "xla_ckpt"


def _primitives(jaxpr, name):
    """Every equation of ``jaxpr`` and its sub-jaxprs whose primitive is
    ``name``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _primitives(sub, name)
    return found


@pytest.mark.parametrize("s,d,heads,tier", [(196, 128, 2, "pallas_short"),
                                            (4, 64, 4, "xla")])
def test_vit_flash_mha_matches_flax_attention(s, d, heads, tier):
    """FlashMHA (same param layout) must reproduce
    nn.MultiHeadDotProductAttention to tolerance — the ViT swap is a drop-in —
    in its output and its parameter gradients, on the one-block kernels (ViT's
    196 tokens reach them by the shape alone) and on the XLA tier (the sizes
    the CPU suite trains ViT at); its parameters are flax's, to the byte."""
    import flax.linen as nn

    from ddw_tpu.models.vit import EncoderBlock, FlashMHA
    from ddw_tpu.ops.flash_attention import _attn_impl

    b = 1
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(b, s, d).astype(np.float32))
    qk = jax.ShapeDtypeStruct((b, heads, s, d // heads), jnp.float32)
    assert _attn_impl(qk, qk, "auto") == tier
    mod = FlashMHA(num_heads=heads, dtype=jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x)
    ref_mod = nn.MultiHeadDotProductAttention(num_heads=heads, dtype=jnp.float32,
                                              name=None)
    ref_params = ref_mod.init(jax.random.PRNGKey(0), x, x)
    assert jax.tree.structure(params) == jax.tree.structure(ref_params)
    for a, r in zip(jax.tree.leaves(params), jax.tree.leaves(ref_params)):
        assert a.dtype == r.dtype and a.shape == r.shape
        assert np.asarray(a).tobytes() == np.asarray(r).tobytes()
    p = params["params"]
    assert p["query"]["kernel"].shape == (d, heads, d // heads)
    assert p["out"]["kernel"].shape == (heads, d // heads, d)

    w = jnp.asarray(rng.randn(b, s, d).astype(np.float32))
    got, got_g = jax.value_and_grad(
        lambda p: jnp.sum(mod.apply(p, x) * w))(params)
    ref, ref_g = jax.value_and_grad(
        lambda p: jnp.sum(ref_mod.apply(p, x, x) * w))(params)
    np.testing.assert_allclose(np.asarray(mod.apply(params, x)),
                               np.asarray(ref_mod.apply(params, x, x)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-4)
    for a, r in zip(jax.tree.leaves(got_g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)

    if tier == "pallas_short":
        # on the kernel tier q, k, v go to the kernels as the projections
        # give them: a ViT block, forward and backward, transposes no
        # [B,S,H,hd] tensor (the kernels' own 2-D tile transposes remain)
        block = EncoderBlock(num_heads=heads, mlp_dim=2 * d,
                             dtype=jnp.float32)
        bp = block.init(jax.random.PRNGKey(1), x, False)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: jnp.sum(block.apply(p, x, False) ** 2)))(bp)
        assert _primitives(jaxpr.jaxpr, "pallas_call")
        assert not [e for e in _primitives(jaxpr.jaxpr, "transpose")
                    if len(e.invars[0].aval.shape) == 4]


def test_one_block_kernels_partition_over_batch_and_heads():
    """The one-block pallas_calls sit under the streaming kernels' partition
    rule: batch over ``data`` and heads over ``model`` (ViT under
    VIT_TP_RULES) run the kernels on local shards — same loss and gradients,
    sharded like the operands, nothing gathered."""
    from ddw_tpu.ops.flash_attention import _attn_impl, flash_mha_seq_major

    mesh = make_mesh(MeshSpec((("data", 2), ("model", 2))),
                     devices=jax.devices()[:4])
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(2, 196, 4, 64).astype(np.float32))
               for _ in range(3))
    assert _attn_impl(*(jax.ShapeDtypeStruct((2, 4, 196, 64), jnp.float32),) * 2,
                      "auto") == "pallas_short"

    def loss(q, k, v):
        return jnp.sum(flash_mha_seq_major(q, k, v, causal=False) ** 2)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    want, want_g = grad(q, k, v)
    rows = NamedSharding(mesh, P("data", None, "model", None))
    sharded = jax.jit(grad, in_shardings=(rows,) * 3)
    got, got_g = sharded(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    for a, b in zip(got_g, want_g):
        assert a.sharding.spec == P("data", None, "model")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    assert "all-gather" not in sharded.lower(q, k, v).compile().as_text()


def test_ring_attention_pallas_arm_matches_full():
    """Ring attention with the Pallas kernel forced per hop (the long-context
    configuration) still matches full attention fwd AND grads — the dispatch
    change must not unpin the kernel-in-ring path."""
    from ddw_tpu.parallel.ring_attention import ring_attention
    from jax.sharding import PartitionSpec as P

    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec

    n = 4
    mesh = make_mesh(MeshSpec((("seq", n),)), devices=jax.devices()[:n])
    q, k, v = _qkv(b=1, h=2, s=32 * n, d=64, seed=11)

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
