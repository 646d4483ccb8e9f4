"""Device mesh + multi-process runtime bootstrap.

Fills the reference's L0 cluster-runtime role (Databricks Spark driver/executors +
barrier scheduling, SURVEY.md §1) and the rendezvous half of Horovod: where the
reference gang-schedules ``np`` Python workers via Spark barrier mode and ``mpirun``
(``Part 1 - Distributed Training/03_model_training_distributed.py:258-263``) and calls
``hvd.init()`` (``:283``), a TPU-native job runs the *same script on every host* and
calls :func:`initialize_distributed` once; gang semantics are inherent to SPMD/XLA.

The mesh is the single source of truth for "who am I / what devices exist":
``hvd.rank()`` -> :func:`process_index`, ``hvd.size()`` -> ``mesh size`` along the data
axis, ``hvd.local_rank()`` -> device ordinal (device pinning,
reference ``:290-295``, is automatic on TPU — each process owns its local chips).

Axis conventions (ddw_tpu.parallel builds on these):
  ``data``     — data parallelism (gradient psum). The only axis the reference uses.
  ``model``    — tensor parallelism.
  ``seq``      — sequence/context parallelism (ring attention).
  ``pipe``     — pipeline stages.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"


def _resolve_sizes(sizes: list[int], total: int, kind: str,
                   what: str) -> list[int]:
    """Shared wildcard algebra: one -1 absorbs the remainder; the product
    must come out to ``total``."""
    wild = [k for k, s in enumerate(sizes) if s == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one {kind} size may be -1")
    prod = int(np.prod([s for s in sizes if s != -1]))
    if wild:
        if total % prod:
            raise ValueError(f"{what} not divisible by fixed {kind} "
                             f"sizes {sizes}")
        sizes = list(sizes)
        sizes[wild[0]] = total // prod
        prod = total
    if prod != total:
        raise ValueError(f"{kind} sizes {sizes} multiply to {prod}, "
                         f"expected {what}")
    return sizes


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape by axis name. Size -1 means "absorb remaining devices"."""

    axes: tuple[tuple[str, int], ...] = ((DATA_AXIS, -1),)

    def resolve(self, n_devices: int) -> tuple[tuple[str, int], ...]:
        sizes = _resolve_sizes([s for _, s in self.axes], n_devices,
                               "axis", f"{n_devices} devices")
        return tuple((a, s) for (a, _), s in zip(self.axes, sizes))


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host bootstrap: replaces Spark-barrier + mpirun + ``hvd.init()``.

    No-op for single-process jobs (the common local/dev case — the ``np=-1`` smoke
    mode of reference ``03_model_training_distributed.py:391-397`` needs no cluster).
    On a TPU pod each host runs this with the same coordinator address; env vars
    ``DDW_COORDINATOR`` / ``DDW_NUM_PROCESSES`` / ``DDW_PROCESS_ID`` are honored so
    the same script works unmodified on every host (SPMD discipline).
    """
    coordinator_address = coordinator_address or os.environ.get("DDW_COORDINATOR")
    if coordinator_address is None:
        return  # single-process
    num_processes = num_processes or int(os.environ.get("DDW_NUM_PROCESSES", "1"))
    process_id = process_id if process_id is not None else int(os.environ.get("DDW_PROCESS_ID", "0"))
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        # The CPU stand-in gang (launcher tests, dev boxes) needs a real
        # cross-process collectives transport; without gloo, XLA:CPU refuses
        # multiprocess computations.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def process_index() -> int:
    """This process's rank (``hvd.rank()`` analog at host granularity)."""
    return jax.process_index()


def process_count() -> int:
    """World size in hosts (``hvd.size()`` analog at host granularity)."""
    return jax.process_count()


def is_coordinator() -> bool:
    """True on the rank-0 process — the only writer of checkpoints/track logs
    (rank-0 discipline, reference ``03_model_training_distributed.py:361-373``)."""
    return jax.process_index() == 0


def local_device_count() -> int:
    return jax.local_device_count()


def global_device_count() -> int:
    return jax.device_count()


@dataclasses.dataclass(frozen=True)
class HybridMeshSpec:
    """Slice-aware mesh shape for multi-slice / multi-pod topologies.

    Each axis is ``(name, dcn_size, ici_size)``: the axis's extent across
    slices (DCN — the slow inter-slice network) times its extent within a
    slice (ICI). The realized mesh axis has size ``dcn_size * ici_size``,
    laid out slice-major: along that axis, consecutive devices sit in the
    same slice and the slice boundary is the largest stride — so XLA's
    hierarchical collectives ride ICI inside a slice and cross DCN only at
    the outermost step (the "data axis outermost over DCN" recipe of the
    scaling playbook; reference's multi-machine analog:
    ``03_model_training_distributed.py:258-263``).

    Latency-sensitive axes refuse to cross slices: ``model`` (Megatron
    all-reduces inside every layer) and ``seq`` (per-block ring hops) raise
    if given ``dcn_size != 1`` — cross-slice TP/SP turns every layer into a
    DCN round-trip. ``data`` (one gradient reduction per step, amortized)
    and ``pipe`` (one activation hop per microbatch, the classic weak-link
    axis) may span slices.

    ``-1`` is allowed once among the dcn sizes (absorb remaining slices) and
    once among the ici sizes (absorb remaining per-slice devices).
    """

    axes: tuple[tuple[str, int, int], ...] = ((DATA_AXIS, -1, -1),)

    _DCN_REFUSED = (MODEL_AXIS, SEQ_AXIS)

    def resolve(self, n_slices: int,
                per_slice: int) -> tuple[tuple[str, int, int], ...]:
        dcn_sizes = _resolve_sizes([d for _, d, _ in self.axes], n_slices,
                                   "dcn", f"{n_slices} slices")
        ici_sizes = _resolve_sizes([i for _, _, i in self.axes], per_slice,
                                   "ici", f"{per_slice} devices per slice")
        # Refuse AFTER wildcard resolution: a -1 that resolves to 1 (single
        # slice) is legal anywhere.
        for (name, _, _), dcn in zip(self.axes, dcn_sizes):
            if name in self._DCN_REFUSED and dcn != 1:
                raise ValueError(
                    f"axis {name!r} with dcn_size={dcn} would put per-layer "
                    f"collectives on the inter-slice network — cross-slice "
                    f"tensor/sequence parallelism is refused; keep "
                    f"{name!r} inside one slice (dcn_size=1) and span "
                    f"slices with 'data' or 'pipe'")
        return tuple((name, d, i) for (name, _, _), d, i
                     in zip(self.axes, dcn_sizes, ici_sizes))


def _device_grid(dims: Sequence[int], devices: Sequence[jax.Device]):
    """``devices`` arranged into ``dims``. On an accelerator ``mesh_utils``
    picks the ICI-aware order and its refusal propagates — a plain reshape
    there would hide why the topology did not fit and may lay collectives
    across the slow links. Host CPU devices have no topology; reshape."""
    devices = list(devices)
    if devices[0].platform == "cpu":
        return np.asarray(devices).reshape(dims)
    return mesh_utils.create_device_mesh(dims, devices=devices)


def device_slice_index(d: jax.Device) -> int:
    """Which slice (pod unit connected by ICI) a device belongs to.

    Real multi-slice TPU backends expose ``slice_index``. An accelerator
    device WITHOUT it must be treated as single-slice: inferring slices
    from ``process_index`` would make every multi-host single-slice pod
    (on a jax build lacking the attribute) look multi-slice and silently
    trade ``mesh_utils``' pod-wide ICI-aware ordering for a host-major
    layout — a perf regression with no DCN to justify it. Only the CPU
    stand-in (launcher gang tests, where each process plays one slice)
    keeps the process-index fallback.
    """
    idx = getattr(d, "slice_index", None)
    if idx is not None:
        return int(idx)
    if d.platform == "cpu":
        return int(d.process_index)
    return 0


def make_hybrid_mesh(
    spec: HybridMeshSpec | Sequence[tuple[str, int, int]] | None = None,
    devices: Sequence[jax.Device] | None = None,
    slice_index_fn=None,
) -> Mesh:
    """Build a DCN-aware :class:`Mesh` over a multi-slice topology.

    Devices group into slices via ``slice_index_fn`` (default
    :func:`device_slice_index`); slices must be equal-sized. Each mesh axis
    realizes as ``dcn_size * ici_size`` laid out slice-major (see
    :class:`HybridMeshSpec`); within a slice, ``mesh_utils`` picks the
    ICI-friendly device order.
    """
    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = HybridMeshSpec()
    if not isinstance(spec, HybridMeshSpec):
        spec = HybridMeshSpec(tuple(spec))
    fn = slice_index_fn or device_slice_index
    groups: dict[int, list[jax.Device]] = {}
    for d in devices:
        groups.setdefault(fn(d), []).append(d)
    sizes = {len(g) for g in groups.values()}
    if len(sizes) > 1:
        raise ValueError(f"unequal slices: {sorted((k, len(g)) for k, g in groups.items())}")
    n_slices, per_slice = len(groups), sizes.pop()
    shape = spec.resolve(n_slices, per_slice)
    dcn_dims = tuple(d for _, d, _ in shape)
    ici_dims = tuple(i for _, _, i in shape)

    ordered = [groups[k] for k in sorted(groups)]
    # [*dcn_dims, *ici_dims] -> interleave (d_j, i_j) pairs -> fuse each pair:
    # along every realized axis, same-slice devices are consecutive and the
    # slice boundary is the outermost stride.
    arr = np.stack([_device_grid(ici_dims, g) for g in ordered]).reshape(
        (*dcn_dims, *ici_dims))
    k = len(shape)
    arr = np.transpose(arr, [a for j in range(k) for a in (j, k + j)])
    arr = arr.reshape([d * i for d, i in zip(dcn_dims, ici_dims)])
    return Mesh(arr, tuple(name for name, _, _ in shape))


def make_data_mesh(devices: Sequence[jax.Device] | None = None,
                   slice_index_fn=None) -> Mesh:
    """The trainers' default 1-D ``data`` mesh — DCN-aware automatically.

    When the devices span multiple slices the axis lays out slice-major
    (:func:`make_hybrid_mesh`): the per-step gradient reduction reduces over
    ICI inside each slice and crosses the DCN once, with zero configuration.
    Single-slice (or unequal-slice, e.g. a truncated ``num_devices``)
    device sets get the plain ICI-optimized mesh.
    """
    if devices is None:
        devices = jax.devices()
    fn = slice_index_fn or device_slice_index
    if len({fn(d) for d in devices}) > 1:
        try:
            return make_hybrid_mesh(((DATA_AXIS, -1, -1),), devices=devices,
                                    slice_index_fn=fn)
        except ValueError:
            pass  # unequal slices: flat mesh is the honest layout
    return make_mesh(MeshSpec(((DATA_AXIS, -1),)), devices=devices)


def make_mesh(
    spec: MeshSpec | Sequence[tuple[str, int]] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named-axis :class:`jax.sharding.Mesh` over the visible devices.

    Default: a 1-D ``data`` mesh over all devices — the reference's only strategy
    (synchronous allreduce-DP, SURVEY.md §2d). ``jax.experimental.mesh_utils`` lays
    devices out so collectives ride ICI within a slice.
    """
    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = MeshSpec()
    if not isinstance(spec, MeshSpec):
        spec = MeshSpec(tuple(spec))
    shape = spec.resolve(len(devices))
    names = tuple(a for a, _ in shape)
    dims = tuple(s for _, s in shape)
    return Mesh(_device_grid(dims, devices), names)
