"""Ring all-reduce as a Pallas TPU kernel — the native collective layer.

The reference's gradient averaging is Horovod's C++ ring allreduce over
NCCL/MPI (``Part 1 - Distributed Training/03_model_training_distributed.py:302``;
SURVEY.md §2c Horovod row, which scopes "an explicit Pallas collective-permute
ring" for this framework's native layer). Production steps use ``lax.psum`` —
XLA already emits optimal ICI collectives — so this kernel exists as the
first-class, inspectable implementation of the same algorithm at the RDMA level,
and as the substrate for fused/overlapped-collective experiments.

Algorithm (Baidu ring allreduce, the one Horovod ships): the array is split into
N chunks; a reduce-scatter phase circulates running partial sums N-1 hops around
the ring (each device ends owning the full sum of one chunk), then an all-gather
phase circulates the completed chunks N-1 hops. Communication per device is
2·(N-1)/N · bytes — bandwidth-optimal.

Mapping to TPU:
- each hop is one ``pltpu.make_async_remote_copy`` to the right neighbor over
  ICI, with DMA send/recv semaphores pairing the transfer;
- every hop lands in its own comm-buffer slot (no slot reuse -> no cross-step
  data race, no per-step barrier; one neighbor barrier at kernel entry is the
  only global sync);
- accumulation happens in VMEM between hops (the chunk never round-trips HBM).

Call :func:`ring_all_reduce_pallas` inside ``shard_map`` binding the named
axis (multi-axis meshes are fine — RDMA hops use MESH addressing along that
axis). On the CPU backend it runs under the Pallas TPU interpreter
(cross-device DMA simulation), so the same kernel is exercised by the CPU test
suite; on any other backend Mosaic compiles it (:mod:`ddw_tpu.ops.backend`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddw_tpu.ops.backend import interpret_by_default

_LANE = 128  # TPU lane tile; chunks are padded to this multiple
_VMEM_BUDGET_BYTES = 8 * 2**20  # per-kernel budget for in + out + comm scratch


def ring_chunks(x: jax.Array, n: int, lane: int = 1) -> jax.Array:
    """Ring framing shared by the ppermute and RDMA rings: flatten and zero-pad
    ``x`` into ``(n, chunk)`` with ``chunk`` a multiple of ``lane``."""
    flat = x.reshape(-1)
    chunk = -(-flat.size // n)           # ceil
    chunk = -(-chunk // lane) * lane
    flat = jnp.pad(flat, (0, n * chunk - flat.size))
    return flat.reshape(n, chunk)


def ring_unchunk(out: jax.Array, orig_shape: tuple[int, ...], size: int) -> jax.Array:
    """Inverse of :func:`ring_chunks`: drop padding, restore the shape."""
    return out.reshape(-1)[:size].reshape(orig_shape)


def _kernel(x_ref, o_ref, snd_buf, rs_buf, ag_buf, rs_send, rs_recv, ag_send,
            ag_recv, *, axis_name: str, n: int):
    me = lax.axis_index(axis_name)
    right = lax.rem(me + 1, n)
    left = lax.rem(me + n - 1, n)

    # Entry barrier with both neighbors: no RDMA may land before the target's
    # kernel is running and its buffers exist. MESH addressing ({axis: index})
    # targets the neighbor along axis_name with all other mesh coords fixed —
    # correct on multi-axis meshes (a plain LOGICAL id would be wrong there:
    # the data-axis neighbor of device 0 on a (data=2, seq=4) mesh is logical
    # device 4, not 1).
    barrier = pltpu.get_barrier_semaphore()
    for nb in (left, right):
        pltpu.semaphore_signal(barrier, inc=1, device_id={axis_name: nb},
                               device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 2)

    o_ref[...] = x_ref[...]

    def send(c_send, dst, send_sem, recv_sem):
        # Stage the outgoing chunk in VMEM: the RDMA source must be VMEM, and
        # the buffer is safe to reuse next hop because rdma.wait() includes
        # local send completion.
        snd_buf[...] = o_ref[pl.ds(c_send, 1), :]
        rdma = pltpu.make_async_remote_copy(
            src_ref=snd_buf, dst_ref=dst, send_sem=send_sem, recv_sem=recv_sem,
            device_id={axis_name: right},
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma.start()
        rdma.wait()  # local send done AND this step's chunk arrived from left

    # Reduce-scatter: at hop k every device forwards its running sum of chunk
    # (me - k) and folds the arriving partial into chunk (me - k - 1).
    for k in range(n - 1):
        c_send = lax.rem(me - k + n, n)
        c_recv = lax.rem(me - k - 1 + n, n)
        send(c_send, rs_buf.at[k], rs_send.at[k], rs_recv.at[k])
        o_ref[pl.ds(c_recv, 1), :] = o_ref[pl.ds(c_recv, 1), :] + rs_buf[k]
    # chunk (me + 1) % n now holds the full sum on this device.

    # All-gather: circulate completed chunks; hop k sends chunk (me + 1 - k),
    # receives chunk (me - k) into place.
    for k in range(n - 1):
        c_send = lax.rem(me + 1 - k + n, n)
        c_recv = lax.rem(me - k + n, n)
        send(c_send, ag_buf.at[k], ag_send.at[k], ag_recv.at[k])
        o_ref[pl.ds(c_recv, 1), :] = ag_buf[k]


def ring_all_reduce_pallas(x: jax.Array, axis_name: str,
                           interpret=None,
                           collective_id: int = 7) -> jax.Array:
    """Sum-allreduce ``x`` over the named mesh axis via the RDMA ring kernel.

    Must run inside ``shard_map`` binding ``axis_name``; every participant must
    pass the same-shaped ``x``. ``interpret`` may be a bool or a
    ``pltpu.InterpretParams`` (e.g. ``detect_races=True``); ``None`` selects
    the Pallas TPU interpreter on the CPU backend only, so tests cover the
    kernel on a CPU mesh and every device backend compiles it.
    """
    n = axis_size(axis_name)
    if n == 1:
        return x
    if interpret is None:
        interpret = interpret_by_default()
    if interpret is True:
        interpret = pltpu.InterpretParams()

    orig_shape, orig_dtype = x.shape, x.dtype
    acc_dtype = jnp.float32 if orig_dtype in (jnp.bfloat16, jnp.float16) else orig_dtype
    x2d = ring_chunks(x.astype(acc_dtype), n, lane=_LANE)
    chunk = x2d.shape[1]

    def one_ring(seg):
        seg_chunk = seg.shape[1]
        scratch = [
            pltpu.VMEM((1, seg_chunk), acc_dtype),          # snd_buf
            pltpu.VMEM((n - 1, 1, seg_chunk), acc_dtype),   # rs_buf
            pltpu.VMEM((n - 1, 1, seg_chunk), acc_dtype),   # ag_buf
            pltpu.SemaphoreType.DMA((n - 1,)),              # rs_send
            pltpu.SemaphoreType.DMA((n - 1,)),              # rs_recv
            pltpu.SemaphoreType.DMA((n - 1,)),              # ag_send
            pltpu.SemaphoreType.DMA((n - 1,)),              # ag_recv
        ]
        return pl.pallas_call(
            functools.partial(_kernel, axis_name=axis_name, n=n),
            out_shape=jax.ShapeDtypeStruct((n, seg_chunk), acc_dtype),
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                collective_id=collective_id, has_side_effects=True),
            interpret=interpret,
        )(seg)

    # VMEM budget: in + out (n*chunk each) + comm scratch (~2n*chunk) live at
    # once, so large arrays run as sequential chunk segments. Segments chain
    # through lax.optimization_barrier (a data edge the simplifier cannot fold
    # away, unlike mul-by-zero on integer dtypes) so XLA cannot overlap two
    # ring kernels sharing one collective_id/barrier semaphore.
    elem = jnp.dtype(acc_dtype).itemsize
    max_seg = max(_LANE, _VMEM_BUDGET_BYTES // (4 * n * elem) // _LANE * _LANE)
    if chunk <= max_seg:
        out = one_ring(x2d)
    else:
        parts = []
        for s in range(0, chunk, max_seg):
            seg = lax.dynamic_slice_in_dim(x2d, s, min(max_seg, chunk - s), axis=1)
            if parts:
                seg, _ = lax.optimization_barrier((seg, parts[-1]))
            parts.append(one_ring(seg))
        out = jnp.concatenate(parts, axis=1)
    return ring_unchunk(out, orig_shape, x.size).astype(orig_dtype)
