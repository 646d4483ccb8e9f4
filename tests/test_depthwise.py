"""Pallas depthwise 3x3 kernel vs the XLA grouped conv: forward and both
gradients. ``impl="pallas"`` runs the kernel in the interpreter here because
the test backend is the CPU (ddw_tpu.ops.backend; the same pinning discipline
as the flash-attention kernels in test_ops_parallel.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddw_tpu.ops.depthwise_conv import _xla_depthwise, depthwise_conv3x3


@pytest.mark.parametrize("shape", [(2, 8, 8, 8), (1, 14, 10, 16)])
def test_forward_matches_xla(shape):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    w = jnp.asarray(rng.randn(3, 3, shape[-1]).astype(np.float32))
    ref = _xla_depthwise(x, w, 1)
    got = depthwise_conv3x3(x, w, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gradients_match_xla():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 8, 8, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(3, 3, 8).astype(np.float32))

    def loss_pallas(x, w):
        y = depthwise_conv3x3(x, w, impl="pallas")
        return jnp.sum(jnp.sin(y))

    def loss_xla(x, w):
        return jnp.sum(jnp.sin(_xla_depthwise(x, w, 1)))

    gx_p, gw_p = jax.grad(loss_pallas, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(loss_xla, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw_p), np.asarray(gw_r),
                               rtol=1e-4, atol=1e-4)


def test_stride2_and_refusals():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(1, 8, 8, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(3, 3, 8).astype(np.float32))
    out = depthwise_conv3x3(x, w, stride=2)  # the default impl is xla
    assert out.shape == (1, 4, 4, 8)
    with pytest.raises(ValueError, match="stride 1"):
        depthwise_conv3x3(x, w, stride=2, impl="pallas")
    with pytest.raises(ValueError, match=r"w must be \[3, 3, C\]"):
        depthwise_conv3x3(x, jnp.zeros((5, 5, 8)), impl="xla")
    with pytest.raises(ValueError, match="channel mismatch"):
        depthwise_conv3x3(x, jnp.zeros((3, 3, 4)), impl="xla")
    with pytest.raises(ValueError, match="unknown impl"):
        depthwise_conv3x3(x, w, impl="cudnn")
    with pytest.raises(ValueError, match="unknown impl"):
        depthwise_conv3x3(x, w, impl="auto")  # no dispatch that picks for you


@pytest.mark.slow  # tier-1 budget (PR 18): the mobilenet-level composition
                   # of the dw units above — same pallas path, so on boxes
                   # where the interpreter units fail this fails identically;
                   # 14s of tier-1 for no extra signal.
def test_mobilenet_dw_impl_preserves_function_and_checkpoint():
    """dw_impl='pallas' keeps the exact param tree and the model function
    (stride-2 depthwise layers use XLA inside the same flag: the kernel has
    no strided form)."""
    from ddw_tpu.models.registry import build_model
    from ddw_tpu.utils.config import ModelCfg

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 32, 32, 3).astype(np.float32))
    base = dict(name="mobilenet_v2", num_classes=5, dropout=0.0,
                freeze_base=False, dtype="float32")
    m0 = build_model(ModelCfg(**base))
    m1 = build_model(ModelCfg(**base, dw_impl="pallas"))
    v = m0.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    v1 = m1.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    assert jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(v1)
    y0 = m0.apply(v, x, train=False)
    y1 = m1.apply(v, x, train=False)  # pallas model runs the xla-trained params
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-4, atol=1e-4)


def test_bf16_inputs_accumulate_f32():
    rng = np.random.RandomState(3)
    x32 = rng.randn(2, 8, 8, 8).astype(np.float32)
    w32 = rng.randn(3, 3, 8).astype(np.float32)
    got = depthwise_conv3x3(jnp.asarray(x32, jnp.bfloat16),
                            jnp.asarray(w32, jnp.bfloat16),
                            impl="pallas")
    assert got.dtype == jnp.bfloat16
    ref = _xla_depthwise(jnp.asarray(x32), jnp.asarray(w32), 1)
    # bf16 inputs, f32 accumulation: agreement to bf16 resolution
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)
