"""The fused passes of a hyper-connection (``ddw_tpu/ops/hyper_connection.py``,
kernels interpreted on the CPU) held to the ``jnp`` forms of
``ddw_tpu/models/lm.py``: a sublayer's input and output, the gradients of the
streams, the sublayer's output and every parameter, at four streams of a width
that tiles; the dispatch by shape; the counter that says which path ran; and a
whole train step with and without rematerialisation on the fused path.

Tolerances. The streams are bfloat16 on both sides and every sum is float32,
so what the two paths may differ by is the order of float32 sums, the 2^-17
the product with ``phi`` keeps of its weight (a bfloat16 pair), and the one
bfloat16 rounding at the end of a pass falling the other way: outputs and
gradients in the streams' dtype agree within one bfloat16 step of their
largest entry (``2^-7`` of it), float32 coefficients within ``2e-5`` and
float32 parameter gradients within ``2e-3`` of their largest entry (the
sublayer between read and write sees inputs that differ by that one step).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ddw_tpu.models import lm
from ddw_tpu.models.lm import build_lm
from ddw_tpu.ops import hyper_connection as hc
from ddw_tpu.runtime.mesh import make_data_mesh
from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
from ddw_tpu.utils.config import LayerSpec, LMCfg

N, S, C = 4, 256, 256
BF16_STEP = 2.0 ** -7


class Sublayer(nn.Module):
    """A hyper-connection around a stand-in sublayer: read, ``tanh``, write."""

    layer: LayerSpec

    @nn.compact
    def __call__(self, x):
        h, post, res = lm.HyperConnection(self.layer, name="hc")(x)
        y = jnp.tanh(h.astype(jnp.float32)).astype(x.dtype)
        return lm.hyper_write(x, y, post, res), (h, post, res)


def jnp_forms(monkeypatch):
    """The dispatch steered to the fallback whatever the shape."""
    monkeypatch.setattr(hc, "fuses", lambda x: False)
    monkeypatch.setattr(hc, "sinkhorn_tiles", lambda logits: False)


def seeded(spec, c=C, s=S):
    """Streams, a cotangent and parameters away from their defaults: a seeded
    ``phi`` five times the model's, so that the coefficients move with the token
    and the clamp has something to bite on."""
    keys = jax.random.split(jax.random.PRNGKey(7), 8)
    x = jax.random.normal(keys[0], (1, s, N, c)).astype(jnp.bfloat16)
    g = jax.random.normal(keys[1], (1, s, N, c)).astype(jnp.bfloat16)
    k = N * (N + 2)
    params = {"hc": {
        "phi": 0.1 * jax.random.normal(keys[2], (N * c, k)),
        "alpha": jnp.asarray([0.7, -0.4, 0.9]),
        "bias": 0.3 * jax.random.normal(keys[3], (k,)),
        "norm": {"scale": 1.0 + 0.1 * jax.random.normal(keys[4], (N * c,))}}}
    return Sublayer(spec), params, x, g


def run(model, params, x, g):
    def loss(params, x):
        out, seen = model.apply({"params": params}, x)
        return jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32)), (
            out, seen)

    (_, (out, seen)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(params, x)
    return out, seen, grads


def close(a, b, share, what):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, atol=share * np.abs(b).max(), rtol=0,
                               err_msg=what)


@pytest.mark.parametrize("diag,clamp", [(0.0, (-30.0, 30.0)),
                                        (1.5, (-30.0, 30.0)),
                                        (1.5, (-0.5, 0.75))])
def test_fused_passes_match_the_jnp_forms(monkeypatch, diag, clamp):
    spec = LayerSpec(hyper_streams=N, hyper_res_diag=diag,
                     hyper_res_clamp=clamp)
    model, params, x, g = seeded(spec)
    assert hc.fuses(x)
    out, (h, post, res), (dp, dx) = run(model, params, x, g)
    jnp_forms(monkeypatch)
    ref_out, (ref_h, ref_post, ref_res), (ref_dp, ref_dx) = run(
        model, params, x, g)
    if clamp[1] < 1.0:          # the clamp bites: logits lie beyond it
        logits = by_hand(params, x, spec)[2] + diag * jnp.eye(N)
        assert float(jnp.mean((logits > clamp[1]) | (logits < clamp[0]))) > 0.3
    close(h, ref_h, BF16_STEP, "the sublayer's input")
    close(post, ref_post, 2e-5, "h_post")
    close(res, ref_res, 2e-5, "h_res")
    close(out, ref_out, BF16_STEP, "the streams after the sublayer")
    close(dx, ref_dx, BF16_STEP, "dx")
    for name in ("phi", "alpha", "bias"):
        close(dp["hc"][name], ref_dp["hc"][name], 2e-3, "d" + name)
    close(dp["hc"]["norm"]["scale"], ref_dp["hc"]["norm"]["scale"], 2e-3,
          "the norm's gain")
    assert float(jnp.max(jnp.abs(ref_dp["hc"]["alpha"]))) > 0


def test_the_write_pass_gives_y_and_the_coefficients_their_gradients(
        monkeypatch):
    """``hyper_write`` alone, where ``y`` is an input of its own: ``dx``,
    ``dy``, ``dh_post`` and ``dh_res`` from one pass over ``dx'``, ``x``,
    ``y``."""
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(keys[0], (1, S, N, C)).astype(jnp.bfloat16)
    y = jax.random.normal(keys[1], (1, S, C)).astype(jnp.bfloat16)
    g = jax.random.normal(keys[2], x.shape).astype(jnp.bfloat16)
    post = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (1, S, N)))
    res = jax.nn.softmax(jax.random.normal(keys[4], (1, S, N, N)), -1)

    def grads():
        return jax.jit(jax.grad(lambda *a: jnp.sum(
            lm.hyper_write(*a).astype(jnp.float32) * g.astype(jnp.float32)),
            argnums=(0, 1, 2, 3)))(x, y, post, res)

    fused = grads()
    jnp_forms(monkeypatch)
    for name, a, b in zip(("dx", "dy", "dh_post", "dh_res"), fused, grads()):
        close(a, b, BF16_STEP if a.dtype == jnp.bfloat16 else 2e-5, name)


def by_hand(params, x, spec):
    """The coefficients and the read written out once more, float32: ``h``,
    ``h_post``, the mixing matrix's logits before the diagonal constant and
    the clamp."""
    _, s, n, c = x.shape
    p = params["hc"]
    flat = x.reshape(1, s, n * c).astype(jnp.float32)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                              + spec.norm_eps) * p["norm"]["scale"]
    gain = jnp.repeat(p["alpha"], jnp.asarray([n, n, n * n]))
    raw = jnp.matmul(xt, p["phi"], precision="highest") * gain + p["bias"]
    h = jnp.einsum("bsn,bsnc->bsc", jax.nn.sigmoid(raw[..., :n]),
                   x.astype(jnp.float32))
    return (h, 2.0 * jax.nn.sigmoid(raw[..., n:2 * n]),
            raw[..., 2 * n:].reshape(1, s, n, n))


@pytest.mark.parametrize("c,fused", [(C, 1.0), (48, 0.0)])
def test_the_dispatch_follows_the_shape_and_the_counter_says_so(c, fused):
    """A width off the 128 lanes takes the ``jnp`` forms and gives what they
    give, written out here once more by hand; the counter reads which."""
    spec = LayerSpec(hyper_streams=N, hyper_res_diag=1.5)
    model, params, x, _ = seeded(spec, c=c, s=128)
    assert hc.fuses(x) == bool(fused)
    (out, (h, post, res)), mods = jax.jit(lambda p, x: model.apply(
        {"params": p}, x, mutable=["intermediates"]))(params, x)
    counters = mods["intermediates"]["hc"]["counters"][0]
    assert float(counters["hc_fused_share"]) == fused
    ref_h, ref_post, logits = by_hand(params, x, spec)
    m = jnp.exp(logits + 1.5 * jnp.eye(N))
    for _ in range(spec.hyper_sinkhorn_iters):
        m = m / (m.sum(-1, keepdims=True) + spec.hyper_eps)
        m = m / (m.sum(-2, keepdims=True) + spec.hyper_eps)
    close(h, ref_h, BF16_STEP, "h")
    close(res, m, 2e-5, "h_res")
    close(post, ref_post, 2e-5, "h_post")
    assert float(counters["hc_res_offdiag_share"]) == pytest.approx(
        float(jnp.mean(1.0 - jnp.trace(m, axis1=-2, axis2=-1) / N)), abs=1e-5)


def test_sinkhorn_on_the_lanes_is_the_loop(monkeypatch):
    """The kernel's rounds and their derivative against the loop's, float32
    to 1e-6 of the largest entry; 96 tokens do not tile and take the loop."""
    logits = (jax.random.normal(jax.random.PRNGKey(5), (2, 128, N, N))
              + 1.5 * jnp.eye(N))
    g = jax.random.normal(jax.random.PRNGKey(6), logits.shape)
    assert hc.sinkhorn_tiles(logits) and not hc.sinkhorn_tiles(logits[:, :48])

    def both():
        return jax.jit(jax.value_and_grad(lambda l: jnp.sum(
            lm.sinkhorn(l, 20, 1e-6) * g)))(logits)

    value, grad = both()
    jnp_forms(monkeypatch)
    ref_value, ref_grad = both()
    assert float(value) == pytest.approx(float(ref_value), rel=1e-5)
    close(grad, ref_grad, 1e-5, "d logits")


@pytest.fixture(scope="module")
def stepped():
    """One step of a hyper-connected model whose streams tile (hidden 128,
    2 x 64 tokens, bfloat16) with ``remat`` none and full."""
    runs = {}
    for remat in ("none", "full"):
        cfg = LMCfg(vocab_size=64, max_len=64, hidden=128, depth=1,
                    num_heads=2, mlp_dim=128, dtype="bfloat16", remat=remat,
                    layer=LayerSpec(hyper_streams=N, hyper_res_diag=1.5))
        model = build_lm(cfg)
        tx = optax.adam(1e-3)
        state = init_lm_state(model, tx, jax.random.PRNGKey(0))
        step = make_lm_train_step(
            model, tx, make_data_mesh(devices=jax.devices()[:1]),
            seq_axis=None, donate=False)
        tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 65), 0, 64)
        runs[remat] = (state,) + step(state, tokens[:, :-1], tokens[:, 1:],
                                      jax.random.PRNGKey(0))
    return runs


def test_a_step_on_the_fused_path_and_remat_changes_nothing(stepped):
    """Every sublayer of the step took the fused passes, the parameter tree
    has today's names and shapes, every hyper-connection leaf moved, and
    ``remat="full"`` gives the step ``remat="none"`` gives."""
    state, new, metrics = stepped["full"]
    assert float(metrics["layers"]["hc_fused_share"]) == 1.0
    assert float(metrics["layers"]["hc_sinkhorn_error"]) < 1e-5
    assert 0.3 < float(metrics["layers"]["hc_res_offdiag_share"]) < 0.5
    block = state.params["backbone_block0"]
    assert sorted(block["hc_attn"]) == ["alpha", "bias", "norm", "phi"]
    assert {k: v.shape for k, v in block["hc_mlp"].items()
            if k != "norm"} == {"alpha": (3,), "bias": (24,),
                                "phi": (512, 24)}
    assert block["hc_mlp"]["norm"]["scale"].shape == (512,)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         new.params, state.params)
    for name in ("hc_attn", "hc_mlp"):
        assert min(jax.tree.leaves(moved["backbone_block0"][name])) > 0
    for a, b in zip(jax.tree.leaves(stepped["none"][1].params),
                    jax.tree.leaves(new.params)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    assert float(stepped["none"][2]["loss"]) == pytest.approx(
        float(metrics["loss"]), rel=1e-6)
