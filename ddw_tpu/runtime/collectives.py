"""Collective communication primitives — the Horovod-core role, compiled into the step.

The reference's three collective uses (SURVEY.md §2b/§5 "Distributed communication
backend") map 1:1 onto XLA collectives over ICI/DCN:

- gradient averaging: ``hvd.DistributedOptimizer(optimizer)``
  (``Part 1 - Distributed Training/03_model_training_distributed.py:302``)
  -> :func:`all_reduce_mean` of the grad pytree inside the jitted step;
- rank-0 weight broadcast: ``BroadcastGlobalVariablesCallback(0)`` (``:308``)
  -> :func:`broadcast_from` (psum of a rank-masked tree) — though under SPMD,
  identical-seed init usually makes it unnecessary;
- metric averaging: ``MetricAverageCallback`` (``:313``) -> :func:`all_reduce_mean`
  on the epoch metrics.

There is no daemon, no tensor-fusion buffer, no background coordinator thread:
everything here is traced into the XLA program, which fuses and schedules the
collectives itself (Horovod's Tensor Fusion falls out of XLA fusion). The in-tree
"native collective" exists at two levels: :func:`ring_all_reduce` (``ppermute``
ring — XLA emits the transfers) and :func:`ring_all_reduce_pallas`
(:mod:`ddw_tpu.ops.ring_reduce` — hand-written RDMA hops, the Horovod-core
analog all the way down to the semaphores). The train steps' own gradient mean
is :func:`ddw_tpu.parallel.collectives.grad_mean`, which also carries the
compiler options under which the reduce rides beneath the backward pass.

All functions take an ``axis_name`` and must be called under ``shard_map``/``pmap``
binding that name.
"""

from __future__ import annotations

from typing import Any, TypeVar

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

T = TypeVar("T")


def all_reduce_sum(tree: T, axis_name: str, impl: str = "psum") -> T:
    """Sum a pytree across ``axis_name`` (allreduce-sum on every participant).

    ``impl``: ``psum`` (XLA collective, production default), ``ring`` (in-tree
    ``ppermute`` ring), or ``pallas`` (RDMA ring kernel,
    :func:`ring_all_reduce_pallas`).
    """
    if impl == "psum":
        return jax.tree.map(lambda x: lax.psum(x, axis_name), tree)
    if impl == "ring":
        return jax.tree.map(lambda x: ring_all_reduce(x, axis_name), tree)
    if impl == "pallas":
        # All leaf kernels share one collective_id (hence one barrier
        # semaphore), so two of them must never be in flight at once: chain
        # each leaf's input on the previous leaf's output through
        # lax.optimization_barrier — the same data-edge serialization the
        # segmented path inside ring_all_reduce_pallas uses. Without it the
        # leaves have no data dependency and XLA may overlap them on real TPU,
        # cross-signaling barrier/DMA semaphores (interpret-mode CPU tests run
        # kernels serially and cannot catch that).
        leaves, treedef = jax.tree.flatten(tree)
        reduced = []
        for leaf in leaves:
            if reduced:
                leaf, _ = lax.optimization_barrier((leaf, reduced[-1]))
            reduced.append(ring_all_reduce_pallas(leaf, axis_name))
        return jax.tree.unflatten(treedef, reduced)
    raise KeyError(f"unknown allreduce impl {impl!r} (have psum, ring, pallas)")


def all_reduce_mean(tree: T, axis_name: str) -> T:
    """Mean a pytree across ``axis_name`` — gradient averaging / MetricAverage role."""
    return jax.tree.map(lambda x: lax.pmean(x, axis_name), tree)


def broadcast_from(tree: T, axis_name: str, root: int = 0) -> T:
    """Broadcast ``root``'s values to every participant along ``axis_name``.

    The ``BroadcastGlobalVariablesCallback(0)`` analog: mask all but ``root`` to zero
    and psum. Under SPMD this is only needed when per-rank state may have diverged
    (e.g. after independent host-side restores from different files).
    """
    idx = lax.axis_index(axis_name)

    def _bcast(x):
        mask = (idx == root).astype(x.dtype)
        return lax.psum(x * mask, axis_name)

    return jax.tree.map(_bcast, tree)


def all_gather_axis(x: jax.Array, axis_name: str, tiled: bool = False) -> jax.Array:
    """Gather shards from every participant along ``axis_name``."""
    return lax.all_gather(x, axis_name, tiled=tiled)


def ring_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Explicit ring allreduce via ``ppermute`` — Horovod's ring algorithm, in-tree.

    Reduce-scatter phase then all-gather phase, each N-1 ``ppermute`` steps around
    the ring; communication-optimal (2·(N-1)/N · bytes). XLA's native ``psum``
    already lowers to this class of algorithm on TPU ICI, so this exists as the
    first-class, testable "native collective" component (SURVEY.md §2c Horovod row),
    and as the substrate for overlap experiments. Numerically identical to
    ``lax.psum`` up to summation order.

    Arrays whose size is not divisible by the axis size are zero-padded for the
    ring and sliced back; returns the full reduced array on every participant.
    """
    from ddw_tpu.ops.ring_reduce import ring_chunks

    n = axis_size(axis_name)
    if n == 1:
        return x
    me = lax.axis_index(axis_name)
    orig_shape = x.shape
    chunks = ring_chunks(x, n)  # chunk c is reduced by rank (c-1) % n

    perm = [(i, (i + 1) % n) for i in range(n)]

    # Reduce-scatter: n-1 ppermute steps around the ring (python loop — n is static
    # at trace time, it's a mesh axis size). At step k each rank forwards its running
    # partial sum and folds in its own copy of the chunk that just arrived.
    acc = jnp.take(chunks, me, axis=0)
    for k in range(n - 1):
        acc = lax.ppermute(acc, axis_name, perm)
        acc = acc + jnp.take(chunks, (me - k - 1) % n, axis=0)
    # acc on rank r is now the full sum of chunk (r + 1) % n.

    # All-gather phase: circulate each completed chunk n-1 hops so every rank ends
    # with all chunks, then restore chunk order (chunk c completed on rank (c-1)%n).
    gathered = [acc]
    block = acc
    for _ in range(n - 1):
        block = lax.ppermute(block, axis_name, perm)
        gathered.append(block)
    # gathered[k] on rank r is the chunk completed by rank (r - k) % n, i.e. chunk
    # (r - k + 1) % n. Scatter into chunk order.
    out = jnp.zeros_like(chunks)
    for k in range(n):
        out = out.at[(me - k + 1) % n].set(gathered[k])
    from ddw_tpu.ops.ring_reduce import ring_unchunk

    return ring_unchunk(out, orig_shape, x.size)


def ring_all_reduce_pallas(x: jax.Array, axis_name: str, **kwargs) -> jax.Array:
    """RDMA-level ring allreduce (Pallas kernel) — see
    :func:`ddw_tpu.ops.ring_reduce.ring_all_reduce_pallas`."""
    from ddw_tpu.ops.ring_reduce import ring_all_reduce_pallas as _impl

    return _impl(x, axis_name, **kwargs)


def host_all_reduce(tag, value, op: str = "sum", timeout_s: float = 120.0):
    """Host-level cross-RANK reduction over the elastic gang's explicit
    rendezvous topology (:mod:`ddw_tpu.runtime.elastic`) — the MapReduce
    ``reduce`` primitive of DrJAX's framing (PAPERS.md), living OUTSIDE the
    XLA program on purpose.

    Everything above in this module is traced into the jitted step and rides
    the implicit ``jax.distributed`` world: fast, but a dead rank wedges
    every peer inside the collective and the world can only be rebuilt by
    restarting it whole. This primitive is the opposite trade: a
    deterministic, rank-ordered fold over the shared-filesystem control
    plane that PARKS instead of wedging — a dead peer aborts it with
    :class:`~ddw_tpu.runtime.elastic.ElasticRestart`, the survivor re-joins
    the re-formed gang, and a respawned rank participates with no device
    runtime surgery. Use it for the elastic gang's cross-rank sync
    (per-chain metrics, small host gradients, agreement values); keep the
    per-layer hot path on the in-step collectives above. Outside elastic
    mode it degenerates to the identity, so the same fn body runs under the
    launcher's ``np=-1`` smoke mode unchanged."""
    from ddw_tpu.runtime.elastic import host_all_reduce as _impl

    return _impl(tag, value, op=op, timeout_s=timeout_s)
