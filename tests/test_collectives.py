"""Collectives tests on the 8-device CPU mesh (Horovod-core role, SURVEY §2c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ddw_tpu.runtime import collectives
from ddw_tpu.runtime.mesh import make_mesh, MeshSpec


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec((("data", 8),)))


def _smap(fn, mesh, n_out=1):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                                 check_vma=False))


def test_all_reduce_sum_mean(mesh):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def f(xs):
        return collectives.all_reduce_sum(xs, "data"), collectives.all_reduce_mean(xs, "data")

    s, m = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                 out_specs=(P("data"), P("data")), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(s), np.full((8, 1), 28.0))
    np.testing.assert_allclose(np.asarray(m), np.full((8, 1), 3.5))


def test_all_reduce_tree(mesh):
    tree = {"a": np.ones((8, 2), np.float32), "b": np.arange(8, dtype=np.float32).reshape(8, 1)}

    def f(t):
        return collectives.all_reduce_mean(t, "data")

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                                check_vma=False))(tree)
    np.testing.assert_allclose(np.asarray(out["a"]), np.ones((8, 2)))
    np.testing.assert_allclose(np.asarray(out["b"]), np.full((8, 1), 3.5))


def test_broadcast_from_root(mesh):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def f(xs):
        return collectives.broadcast_from(xs, "data", root=3)

    out = _smap(f, mesh)(x)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.0))


def test_ring_all_reduce_matches_psum(mesh):
    rng = np.random.RandomState(0)
    # per-device shard: 8 devices x 16 elements, leading dim divisible by 8
    x = rng.randn(8, 16).astype(np.float32)

    def ring(xs):
        return collectives.ring_all_reduce(xs[0], "data")[None]

    def psum(xs):
        return jax.lax.psum(xs[0], "data")[None]

    got = _smap(ring, mesh)(x)
    want = _smap(psum, mesh)(x)
    # identical up to float32 summation order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_ring_all_reduce_single_axis_size():
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = np.ones((1, 8), np.float32)

    def ring(xs):
        return collectives.ring_all_reduce(xs[0], "data")[None]

    out = jax.jit(shard_map(ring, mesh=mesh1, in_specs=P("data"), out_specs=P("data"),
                                check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), x)


def test_all_gather(mesh):
    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def f(xs):
        return collectives.all_gather_axis(xs[0], "data")[None]

    out = _smap(f, mesh)(x)
    assert np.asarray(out).shape == (8, 8, 1)


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("shape,dtype", [((33,), np.float32),
                                         ((4, 50), np.float32),
                                         ((256,), np.float32)])
def test_pallas_ring_all_reduce_matches_sum(n, shape, dtype):
    """RDMA ring kernel (TPU-interpreted on CPU) == plain sum, all ring sizes."""
    from ddw_tpu.ops.ring_reduce import ring_all_reduce_pallas

    mesh = make_mesh(MeshSpec((("data", n),)), devices=jax.devices()[:n])
    rng = np.random.RandomState(n * 1000 + shape[0])
    x = rng.randn(n, *shape).astype(dtype)

    fn = jax.jit(shard_map(
        lambda xs: ring_all_reduce_pallas(xs[0], "data")[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
    out = np.asarray(fn(x))
    ref = x.sum(axis=0)
    for i in range(n):
        np.testing.assert_allclose(out[i], ref, rtol=1e-5, atol=1e-5)


def test_pallas_ring_all_reduce_bf16_accumulates_f32():
    """bf16 input reduces through an f32 ring (no precision cliff), returns bf16."""
    from ddw_tpu.ops.ring_reduce import ring_all_reduce_pallas

    n = 4
    mesh = make_mesh(MeshSpec((("data", n),)), devices=jax.devices()[:n])
    rng = np.random.RandomState(3)
    x = rng.randn(n, 96).astype(np.float32)
    xb = x.astype(jnp.bfloat16)

    fn = jax.jit(shard_map(
        lambda xs: ring_all_reduce_pallas(xs[0], "data")[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
    out = np.asarray(fn(xb)).astype(np.float32)
    ref = np.asarray(xb).astype(np.float32).sum(axis=0)
    assert out.dtype == np.float32 and fn(xb).dtype == jnp.bfloat16
    np.testing.assert_allclose(out[0], ref, rtol=2e-2, atol=2e-2)


def test_all_reduce_sum_impl_dispatch(mesh):
    """all_reduce_sum(impl=...) routes psum / ppermute-ring / pallas-ring to the
    same answer on a pytree."""
    x = np.arange(16, dtype=np.float32).reshape(8, 2)

    def f(impl):
        return jax.jit(shard_map(
            lambda xs: collectives.all_reduce_sum({"a": xs, "b": xs * 2}, "data",
                                                  impl=impl),
            mesh=mesh, in_specs=P("data"),
            out_specs={"a": P("data"), "b": P("data")}, check_vma=False))(x)

    base = f("psum")
    for impl in ("ring", "pallas"):
        got = f(impl)
        for key in ("a", "b"):
            np.testing.assert_allclose(np.asarray(got[key]),
                                       np.asarray(base[key]), rtol=1e-5)
    with pytest.raises(KeyError, match="unknown allreduce impl"):
        f("nccl")


def test_pallas_ring_race_detector_clean():
    """The interpreter's vector-clock race detector passes over the kernel."""
    from jax.experimental.pallas import tpu as pltpu

    from ddw_tpu.ops.ring_reduce import ring_all_reduce_pallas

    n = 4
    mesh = make_mesh(MeshSpec((("data", n),)), devices=jax.devices()[:n])
    x = np.ones((n, 128), np.float32)
    # detect_races asserts internally on any cross-device read/write race
    params = pltpu.InterpretParams(detect_races=True)
    fn = jax.jit(shard_map(
        lambda xs: ring_all_reduce_pallas(xs[0], "data", interpret=params)[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, np.full((n, 128), n, np.float32))


def test_pallas_ring_all_reduce_multi_axis_mesh():
    """MESH device addressing: reducing over one axis of a (data=2, seq=4) mesh
    must ring within each seq group, not across logical-device order."""
    from ddw_tpu.ops.ring_reduce import ring_all_reduce_pallas

    mesh = make_mesh(MeshSpec((("data", 2), ("seq", 4))),
                     devices=jax.devices()[:8])
    rng = np.random.RandomState(7)
    x = rng.randn(2, 4, 160).astype(np.float32)

    fn = jax.jit(shard_map(
        lambda xs: ring_all_reduce_pallas(xs[0, 0], "seq")[None, None],
        mesh=mesh, in_specs=P("data", "seq"), out_specs=P("data", "seq"),
        check_vma=False))
    out = np.asarray(fn(x))
    # each data row reduces over its own seq group
    for d in range(2):
        ref = x[d].sum(axis=0)
        for s in range(4):
            np.testing.assert_allclose(out[d, s], ref, rtol=1e-5)


def test_pallas_ring_all_reduce_segments_large_arrays(monkeypatch):
    """Arrays over the VMEM budget run as chained sequential ring segments."""
    import ddw_tpu.ops.ring_reduce as rr

    # shrink the budget so a modest array needs several segments:
    # max_seg = max(128, budget // (4*n*4) // 128 * 128) -> 128 elems
    monkeypatch.setattr(rr, "_VMEM_BUDGET_BYTES", 4 * 128 * 4 * 4)
    n = 4
    mesh = make_mesh(MeshSpec((("data", n),)), devices=jax.devices()[:n])
    rng = np.random.RandomState(11)
    x = rng.randn(n, 4 * 560).astype(np.float32)  # chunk 560 -> 5 segments

    fn = jax.jit(shard_map(
        lambda xs: rr.ring_all_reduce_pallas(xs[0], "data")[None],
        mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False))
    out = np.asarray(fn(x))
    ref = x.sum(axis=0)
    for i in range(n):
        np.testing.assert_allclose(out[i], ref, rtol=1e-5, atol=1e-5)
