"""The flash kernels compiled by the TPU's own compiler for a described v5e —
no chip, nothing runs. The interpreter (every other flash test) accepts what
Mosaic refuses: a slice off the tiling, a block it cannot lay out, more VMEM
than a kernel may use. These are the shapes the benchmark's cells run and the
corners of the one-block form (a block that over-runs the array, a key side
padded to 16, head blocks with padding). Keep these tests in this one file:
only one process may load the TPU's library (on-chip-measurement guide, 2)."""

import importlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

fa = importlib.import_module("ddw_tpu.ops.flash_attention")
ik = importlib.import_module("ddw_tpu.ops.indexed_kernels")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Compile without the persistent cache: an executable for a described
    chip is written to it but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


# q [B,Sq,H,D], Sk, causal, dtype
_SHORT = {
    "vit_b16": ((128, 196, 12, 64), 196, False, jnp.bfloat16),
    "lm_384_d128": ((8, 384, 16, 128), 384, True, jnp.bfloat16),
    "rectangular_f32": ((8, 300, 4, 64), 200, True, jnp.float32),
    "three_heads_d48": ((4, 196, 3, 48), 196, False, jnp.bfloat16),
    "one_block_of_512": ((4, 512, 4, 64), 512, True, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(_SHORT))
def test_one_block_kernels_compile_for_v5e(one_chip, case):
    (b, sq, h, d), sk, causal, dtype = _SHORT[case]

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q, k, rows = sds((b, sq, h, d)), sds((b, sk, h, d)), \
        sds((b, h, sq), jnp.float32)
    scale = 1.0 / d ** 0.5
    fwd = _compile(lambda q, k, v: fa._short_forward(
        q, k, v, causal, scale, False), q, k, k)
    bwd = _compile(lambda *a: fa._short_backward(
        *a, causal, scale, False), q, k, k, q, rows, q, rows)
    assert fwd.count("tpu_custom_call") == 1
    assert bwd.count("tpu_custom_call") == 1


# q/k [B,S,H,D] and v [B,S,H,Dv]: the attention of the five cells that run the
# streaming kernels, a chip (where each keeps dQ's sum:
# test_ops_parallel.py::test_dq_home_of_the_cells)
_STREAMING = {
    # both GPT-2 medium cells: one key block, two heads of 64 a lane block
    "gpt2m_d64": ((8, 1024, 16, 64), 64),
    "nemotron_d128": ((2, 8192, 32, 128), 128),
    # latent attention's heads: 192 wide q/k as one and a half lane rows, v
    # at its own 128 (two heads a lane block: 384 and 256 lanes)
    "xing4_192_128": ((1, 4096, 32, 192), 128),
    "joyai_192_128": ((2, 8192, 32, 192), 128),
    "wide_128_64": ((2, 1024, 4, 128), 64),
}


@pytest.mark.parametrize("case", sorted(_STREAMING))
def test_streaming_kernels_compile_for_v5e(one_chip, case, monkeypatch):
    """Mosaic takes the forward kernel and the one-pass backward at the cells'
    shapes, and plans the backward inside the VMEM a kernel gets unasked
    wherever ``_dq_home`` keeps dQ's sum: compiled with that as its limit,
    it would be refused for a byte more."""
    (b, s, h, d), dv = _STREAMING[case]
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, s, h, dv), jnp.bfloat16, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((b, h, s), jnp.float32, sharding=one_chip)
    blocks = (True, 0, 0, d ** -0.5, None, None, False)
    text = _compile(lambda q, k, v: fa._flash_forward(q, k, v, *blocks),
                    q, q, v)
    assert text.count("tpu_custom_call") == 1
    monkeypatch.setattr(fa, "_VMEM_LIMIT", fa._VMEM_UNASKED)
    text = _compile(lambda *a: fa._flash_bwd.__wrapped__(*a, *blocks),
                    q, q, v, v, rows, rows)
    assert text.count("tpu_custom_call") == 1
    assert f'"size":"{fa._VMEM_UNASKED}"' in text
    if d != dv:     # nothing padded or copied on the way in or out
        assert " pad(" not in text


# the hyper-connection's passes (ops/hyper_connection.py) at the shape
# xing4_train_s4096 runs them: [1, 4096, 4, 3584] bfloat16 streams, a token's
# streams side by side; name -> (function of its arrays, their shapes)
def _hyper_cases():
    hc = importlib.import_module("ddw_tpu.ops.hyper_connection")
    t, n, c = 4096, 4, 3584
    d, k = n * c, n * (n + 2)
    bf, f32 = jnp.bfloat16, jnp.float32
    x, y = ((t, d), bf), ((t, c), bf)
    coef, z, lanes = ((t, n + n * n), f32), ((t, k), f32), \
        ((n * n, t // 128, 128), f32)
    sink = lambda kernel: (lambda *a: hc._sinkhorn_call(     # noqa: E731
        kernel, n, 20, 1e-6, False, "hc_sinkhorn", *a))
    return {
        "read_forward": (lambda *a: hc._read_forward(*a, n, 1e-6, False),
                         [x, ((2 * k, d), bf), ((1, k), f32)]),
        "read_backward": (lambda *a: hc._read_backward(*a, n, False),
                          [x, ((k, d), bf), ((1, k), f32), z, ((t, 1), f32),
                           y, z]),
        "write_forward": (lambda *a: hc._write_forward(*a, n, False),
                          [x, y, coef]),
        "write_backward": (lambda *a: hc._write_backward(*a, n, False),
                           [x, y, coef, x]),
        "sinkhorn_forward": (sink(hc._sinkhorn_fwd_kernel), [lanes]),
        "sinkhorn_backward": (sink(hc._sinkhorn_bwd_kernel), [lanes, lanes]),
    }


@pytest.mark.parametrize("case", ["read_forward", "read_backward",
                                  "write_forward", "write_backward",
                                  "sinkhorn_forward", "sinkhorn_backward"])
def test_hyper_connection_passes_compile_for_v5e(one_chip, case):
    """Mosaic takes each pass at the cell's shape inside the VMEM a kernel
    gets unasked (none of them raises its limit)."""
    fn, shapes = _hyper_cases()[case]
    text = _compile(fn, *(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                          for shape, dtype in shapes))
    assert text.count("tpu_custom_call") == 1
    assert "vmem_limit_bytes" not in text


# the chosen-key attention kernels (ops/indexed_kernels.py): S and the
# backward's key block. [2, S, 32 / 4, 128] bfloat16 is keyevl2_train_s8192's
# attention at S = 8,192; 1,536 is a length that 1,024 does not divide
_INDEXED = {"keye_s8192": (8192, 1024), "s1536": (1536, 512)}


@pytest.mark.parametrize("case", sorted(_INDEXED))
def test_indexed_kernels_compile_for_v5e(one_chip, case):
    """Mosaic takes the forward kernel, the one-pass backward and the target
    pass at the code's own blocks inside the VMEM a kernel gets unasked: none
    of them raises its limit (a step whose kernels did never came back from
    the chip, PERF.md section 6, PR 33)."""
    s, bk_bwd = _INDEXED[case]
    b, h, kv, d = 2, 32, 4, 128
    bq, bk, _ = blocks = ik.pick_blocks(s)
    assert blocks == (256, 512, bk_bwd)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, k, mask = sds((b, s, h, d)), sds((b, s, kv, d)), sds((b, s, s), jnp.int8)
    rows = sds((b, kv, h // kv, s), jnp.float32)
    for fn, args, block_k in (
            (ik._forward, (q, k, k, mask), bk),
            (ik._backward, (q, k, k, mask, q, rows, rows), bk_bwd),
            (ik._target, (q, k, mask, rows), bk)):
        text = _compile(lambda *a: fn.__wrapped__(
            *a, d ** -0.5, bq, block_k, False), *args)
        assert text.count("tpu_custom_call") == 1
        assert "vmem_limit_bytes" not in text
