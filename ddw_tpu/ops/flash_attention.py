"""Flash attention as a Pallas TPU kernel.

The reference stack has no attention anywhere (SURVEY.md §5 "Long-context ...
Absent") — this op exists because long-context support is first-class in this
framework: it is the attention of the LM family from 512 tokens up, the
local-block compute of :mod:`ddw_tpu.parallel.ring_attention` (sequence
parallelism) and, in its one-block form for sequences under 512 tokens, the
attention of the ViT family.

Design (Dao et al. flash attention, TPU-first):
- the kernels take q, k, v as the projections produce them, ``[B, S, H, D]``
  viewed ``[B, S, H*D]``: a grid step loads a 128-lane block of it — two heads
  at D = 64 — so every q/k/v/o tile fills its lane rows, nothing is transposed
  or padded in HBM on the way in or out, and the residuals the backward keeps
  are the tensors the model holds anyway. A head of the block is selected by
  zeroing the other heads' lanes of one matmul operand (a 128-deep contraction
  costs the MXU what a 64-deep one does) and by a lane select on the result;
- grid over (batch, head blocks, Q blocks, K blocks); the K blocks are the
  innermost grid dimension, streamed with running max / normalizer /
  accumulator in VMEM scratch, and a loop inside the step walks key sub-blocks
  — O(S) memory instead of the O(S^2) score matrix, scores never leave VMEM;
- blocks are chosen by the code from the sequence lengths (``_pick_block``),
  f32 accumulation with inputs in bf16 or f32;
- causal masking by global position (supports the ring-attention case where this
  rank's K block sits at a rotated global offset);
- backward pass as ONE Pallas kernel: the forward saves the per-row
  logsumexp; a grid step holds a K/V block, streams the Q/dO blocks past it
  and rematerializes p = exp(s - L) sub-block-wise in VMEM once, and dQ, dK
  and dV all come of that tile (``_bwd_kernel``; dQ's sum over the key blocks
  waits in VMEM or HBM by the shapes, ``_dq_home``) — O(S) HBM for the whole
  train step, the S x S matrices never exist in HBM;
- q and k heads may be wider than v heads (latent attention: 192 and 128).
  A lane block then holds the fewest heads whose q/k lanes AND v lanes both
  fill whole 128-lane rows (two: 384 and 256), nothing is padded in HBM, and
  a head whose lanes do not start or end on a lane row is read as the aligned
  window of lane rows around it with the neighbour's lanes zeroed in q (192
  as one and a half lane rows: a 256-deep contraction, which costs the MXU
  what a 192-deep one does); v, dO and the accumulator stay 128 wide a head,
  so no product with v runs at the q/k width (``_head_windows``);
- sequences under 512 tokens take a one-block form of the same kernels (no
  streaming, one backward kernel, nothing padded in HBM; see "Short
  sequences" below);
- ``interpret=None`` means the interpreter on the CPU backend (tests) and the
  Mosaic compiler on every other backend (:mod:`ddw_tpu.ops.backend`).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.custom_partitioning import custom_partitioning
from jax.experimental.pallas import tpu as pltpu

from ddw_tpu.ops.backend import interpret_by_default

_NEG_INF = -1e30
_LANES = 128
# Scoped VMEM a kernel may use: the largest working set _pick_block allows
# (f32 score tiles of 1 MiB, a handful live) passes the 16 MiB default.
_VMEM_LIMIT = 32 * 1024 * 1024
# ... and what one gets unasked, which the code's own blocks stay inside: a
# step of kernels that used 20 MiB of a raised limit once never came back
# (PERF.md section 6, PR 33).
_VMEM_UNASKED = 16 * 1024 * 1024


def _bh_sharding(sharding, ndim):
    """A NamedSharding keeping the suggested batch and heads axes — dims 0 and
    2 of the ``[B, S, H, D]`` operands, dims 0 and 1 of the ``[B, H, Sq]`` rows
    — and replicating the rest: the partition layout the kernels support (seq
    and head_dim must be device-local)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = tuple(sharding.spec) + (None,) * 4
    batch, heads = spec[0], spec[2]
    spec = (batch, None, heads, None) if ndim == 4 else (batch, heads, None)
    return NamedSharding(sharding.mesh, P(*spec))


def _def_bh_partition(fn, impl, rule, out_ndims):
    """Register batch/head-sharded SPMD partitioning on ``fn``.

    GSPMD cannot auto-partition a Mosaic custom call, so without this the
    pjit TP/DP paths (VIT_TP_RULES, LM_TP_RULES shard attention heads over
    ``model``; DP shards batch) would all-gather the operands and run the
    kernel replicated — or fail to lower. The rule declares the batch and
    heads dims freely shardable and everything else need-replication; the
    per-shard lowering is the kernel itself on local shapes. Under shard_map
    (the ring path, the data-parallel LM step) the op is already per-device
    and partitioning never engages."""

    def shardings(arg_shapes):
        like = arg_shapes[0].sharding       # q [B, Sq, H, D]
        return (tuple(_bh_sharding(like, n) for n in out_ndims),
                tuple(_bh_sharding(like, s.ndim) for s in arg_shapes))

    def partition(mesh, arg_shapes, result_shape):
        outs, args = shardings(arg_shapes)
        return mesh, impl, outs, args

    def infer(mesh, arg_shapes, result_shape):
        return shardings(arg_shapes)[0]

    # NB: shardy requires the special-factor indices sorted, i.e. listed in
    # first-appearance order of the rule string (q before d before s).
    fn.def_partition(partition=partition, infer_sharding_from_operands=infer,
                     sharding_rule=rule,
                     need_replication_factors=tuple(
                         f for f in ("q", "d", "s", "e") if f" {f}" in rule))
    return fn


@functools.lru_cache(maxsize=None)
def _partitioned_fwd(causal, q_offset, k_offset, sm_scale, block_q, block_k,
                     interpret, k_valid):
    """(q, k, v) -> (out [B,Sq,H,D], lse [B,H,Sq]) with SPMD partitioning over
    batch/heads. Cached per static config (the custom_partitioning object must
    be built once per config, not per trace)."""

    def impl(q, k, v):
        return _flash_forward(q, k, v, causal, q_offset, k_offset, sm_scale,
                              block_q, block_k, interpret, k_valid)

    fn = custom_partitioning(impl)
    return _def_bh_partition(
        fn, impl, "b q h d, b s h d, b s h e -> b q h e, b h q",
        out_ndims=(4, 3))


def _resolve_defaults(sm_scale, interpret, head_dim):
    """Single place the primal, fwd-rule, and bwd-rule resolve their defaults —
    a divergence here would silently scale/backend the two paths differently."""
    if sm_scale is None:
        sm_scale = 1.0 / float(head_dim) ** 0.5
    if interpret is None:
        interpret = interpret_by_default()
    return sm_scale, interpret


def mha_reference(q, k, v, causal: bool = False, q_offset: int = 0,
                  k_offset: int = 0, sm_scale: float | None = None) -> jnp.ndarray:
    """Plain einsum attention — numerics oracle for the kernel and the VJP
    recompute path. Shapes: q [B,H,Sq,D], k/v [B,H,Sk,D]."""
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / jnp.sqrt(d).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[2])[:, None]
        kpos = k_offset + jnp.arange(k.shape[2])[None, :]
        logits = jnp.where(kpos <= qpos, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)).astype(q.dtype)


def _sub_block_range(q0, k0, block_q: int, block_k: int, sub_k: int,
                     causal: bool, k_valid: int | None):
    """``(n_full, n_vis)``: of the ``block_k // sub_k`` key sub-blocks of one
    (q block, k block) grid step, sub-blocks ``[0, n_full)`` are visible to
    every query row (no mask needed) and ``[n_full, n_vis)`` are crossed by the
    causal diagonal or by the padded tail (mask needed); the rest are skipped.
    ``q0`` / ``k0`` are the global positions of the blocks' first row / key
    (traced under causal or padding, so the bounds are then traced too)."""
    full = vis = block_k
    if causal:
        vis = jnp.clip(q0 + block_q - k0, 0, block_k)    # keys <= the last row
        full = jnp.clip(q0 - k0 + 1, 0, block_k)         # keys <= the first row
    if k_valid is not None:
        valid = jnp.clip(k_valid - k0, 0, block_k)
        vis, full = jnp.minimum(vis, valid), jnp.minimum(full, valid)
    return full // sub_k, (vis + sub_k - 1) // sub_k


def _for_sub_blocks(n_full, n_vis, step):
    """Run ``step(j, masked)`` over the unmasked then the masked sub-blocks."""
    jax.lax.fori_loop(0, n_full, lambda j, _: step(j, False), None)
    if not (isinstance(n_vis, int) and n_vis == n_full):    # nothing masks
        jax.lax.fori_loop(n_full, n_vis, lambda j, _: step(j, True), None)


def _scores(a, b, sm_scale):
    """``a b^T * sm_scale``: operands in the input dtype (bf16 -> full MXU
    rate), f32 accumulation; contracting both minor dims, so no operand is
    transposed in VMEM."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * sm_scale


def _mask_scores(sc, q_start, k_start, causal, k_valid, k_axis: int):
    """The causal + key-padding masks at global positions — shared by the
    forward and the backward kernels so the masking can never desynchronize.
    Keys run along ``k_axis`` of ``sc`` (1 in the forward kernels, 0 in the
    backward ones, which work on transposed scores). ``k_valid`` (static)
    masks keys at global position >= it (the padded tail when the sequence was
    padded up to a block multiple). Only sub-blocks the diagonal or the padded
    tail crosses come here."""
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, sc.shape, k_axis)
    keep = None
    if causal:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, sc.shape,
                                                  1 - k_axis)
        keep = kpos <= qpos
    if k_valid is not None:
        valid = kpos < k_valid
        keep = valid if keep is None else jnp.logical_and(keep, valid)
    return jnp.where(keep, sc, _NEG_INF)


def _finite_ref(ref):
    """The fully-masked-row guard, on the row statistic instead of the score
    tile: a row whose every key is masked keeps its running max (forward) or
    logsumexp (backward) at ~_NEG_INF, where ``s - ref`` would cancel in f32
    (exp -> 1). With the reference at 0 there, ``exp(_NEG_INF - 0)`` is 0, so
    masked rows stay at zero output and zero gradient. Load-bearing in both
    streaming kernels."""
    return jnp.where(ref > _NEG_INF / 2, ref, 0.0)


def _lanes(x, n: int):
    """A lane-replicated ``[rows, 128]`` statistic as ``[rows, n]``."""
    reps = -(-n // _LANES)
    if reps > 1:
        x = jnp.tile(x, (1, reps))
    return x if x.shape[1] == n else x[:, :n]


def _head_masks(heads: int, head_dim: int, width: int):
    """Lane masks ``[1, width]``, one a head of a lane block (None for a block
    that is one head)."""
    if heads == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [jnp.logical_and(lane >= t * head_dim, lane < (t + 1) * head_dim)
            for t in range(heads)]


def _only(mask, x):
    """``x`` with the other heads' lanes zeroed: as a matmul operand contracted
    over the lanes it yields this head's product alone."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _head_windows(heads: int, head_dim: int, width: int):
    """Where each head of a lane block of ``width`` lanes lies, for a kernel
    to read it by: ``(lanes, mask)`` a head. ``lanes`` is the aligned window
    of whole 128-lane rows around the head's ``head_dim`` lanes (a static
    slice; None where that is the whole block, which is every layout with
    equal q/k and v widths; :func:`_at` indexes by it) and ``mask`` the head's
    lanes inside it, ``[1, window]``, None where the head fills its window."""
    if heads == 1 or width <= _LANES:       # one lane row, or one head: whole
        return [(None, mask) for mask in _head_masks(heads, head_dim, width)]
    out = []
    for t in range(heads):
        lo, hi = t * head_dim, (t + 1) * head_dim
        start = lo // _LANES * _LANES
        stop = min(-(-hi // _LANES) * _LANES, width)
        lanes = None if (start, stop) == (0, width) else slice(start, stop)
        mask = None
        if (start, stop) != (lo, hi):
            lane = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, stop - start), 1)
            mask = jnp.logical_and(lane >= lo, lane < hi)
        out.append((lanes, mask))
    return out


def _at(lanes, rows=None):
    """The index of a head's window in a block: all rows or ``rows``, the
    window's ``lanes`` (None: every lane)."""
    if rows is None:
        return ... if lanes is None else (slice(None), lanes)
    return rows, slice(None) if lanes is None else lanes


def _qv_windows(heads: int, head_dim: int, q_width: int, v_dim: int,
                v_width: int):
    """:func:`_head_windows` of the q/k side and of the v side; one list for
    both where the two sides are laid out alike."""
    q_heads = _head_windows(heads, head_dim, q_width)
    if (head_dim, q_width) == (v_dim, v_width):
        return q_heads, q_heads
    return q_heads, _head_windows(heads, v_dim, v_width)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                  heads: int, head_dim: int, v_dim: int, block_q: int,
                  block_k: int, sub_k: int, causal: bool, q_offset: int,
                  k_offset: int,
                  sm_scale: float, k_valid: int | None):
    """One (batch, head block, q-block, k-block) grid step of online-softmax
    attention, for the ``heads`` heads of the lane block in turn.

    The K loop is a GRID dimension (innermost), so Mosaic double-buffers the
    K/V block DMAs across steps; inside a step a loop walks the ``sub_k``-wide
    key sub-blocks, so a block can be large (few grid steps) while causal
    skipping and the masking work stay at sub-block granularity. The running
    (max, normalizer) of each head live lane-replicated in ``[block_q, 128]``
    VMEM scratch and the accumulator of all of them in ``[block_q, heads*d]``,
    persisting along the k dimension: initialized at kb==0, written to the
    output block at the last kb. QK^T and PV run in the input dtype (bf16 ->
    full MXU rate) with f32 accumulation (preferred_element_type); softmax
    bookkeeping is f32 on the VPU. Sub-blocks wholly in the causal future (or
    the padded tail) are skipped."""
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    num_kb = pl.num_programs(3)
    width = acc_scr.shape[-1]
    q_heads, v_heads = _qv_windows(heads, head_dim, q_ref.shape[-1], v_dim,
                                   width)
    # the heads' lanes of the whole output block, for the last step
    masks = ([mask for _, mask in v_heads]
             if all(lanes is None for lanes, _ in v_heads)
             else _head_masks(heads, v_dim, width))

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q0 = q_offset + qi * block_q
    k0 = k_offset + kb * block_k
    bounds = _sub_block_range(q0, k0, block_q, block_k, sub_k, causal, k_valid)

    for t, ((qw, qmask), (vw, vmask)) in enumerate(zip(q_heads, v_heads)):
        q = _only(qmask, q_ref[_at(qw)])                   # [block_q, window]

        def _attend(j, masked):
            ks = pl.ds(pl.multiple_of(j * sub_k, sub_k), sub_k)
            s = _scores(q, k_ref[_at(qw, ks)], sm_scale)      # [block_q, sub_k]
            if masked:
                s = _mask_scores(s, q0, k0 + j * sub_k, causal, k_valid, 1)
            m_prev = m_scr[t]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # guard keeps l at 0 on fully-masked rows so _finalize emits zeros
            m_ref = _finite_ref(m_new) if masked else m_new
            p = jnp.exp(s - _lanes(m_ref, sub_k))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[t] = alpha * l_scr[t] + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p.astype(q.dtype), v_ref[_at(vw, ks)],
                         preferred_element_type=jnp.float32)
            acc = acc_scr[_at(vw)]
            new = acc * _lanes(alpha, acc.shape[-1]) + pv  # this head's lanes
            acc_scr[_at(vw)] = (new if vmask is None
                              else jnp.where(vmask, new, acc))
            m_scr[t] = m_new

        _for_sub_blocks(*bounds, _attend)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        norm = None
        for t, mask in enumerate(masks):
            l = jnp.maximum(l_scr[t], 1e-30)
            l_wide = _lanes(l, width)
            norm = l_wide if norm is None else jnp.where(mask, l_wide, norm)
            # logsumexp residual for the Pallas backward (FA2): L = m + log(l).
            # Fully-masked rows keep L ~ _NEG_INF; the backward re-zeroes p
            # there (_finite_ref). Stored as a lane-dense row: the replicated
            # [block_q, 128] statistic transposed, one sublane of it kept.
            lse_ref[t:t + 1, :] = (m_scr[t] + jnp.log(l)).T[:1]
        o_ref[...] = (acc_scr[...] / norm).astype(o_ref.dtype)


def _compiler_params():
    # batch, head-block and q-block steps are independent (scratch re-inits at
    # the innermost dim's first step); only the innermost dim carries state.
    # Declaring that lets Mosaic overlap DMA and compute across grid steps
    # instead of serializing the whole grid.
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _head_blocks(h: int, d: int):
    """How ``h`` heads of dim ``d`` are laid over 128-lane blocks of the
    ``[B, S, H*D]`` view: ``(heads a block, padded d, padded h)``. A d that
    divides 128 packs 128 // d heads a block; any other is zero-padded up to
    the next that does (or to a multiple of 128), which changes no score, and
    the heads up to a whole block."""
    if d >= _LANES:
        return 1, -(-d // _LANES) * _LANES, h
    per = _LANES // d
    while _LANES % per:
        per -= 1
    return per, _LANES // per, -(-h // per) * per


def _head_layout(h: int, d: int, dv: int):
    """:func:`_head_blocks` for q/k heads of ``d`` and v heads of ``dv``:
    ``(heads a block, padded d, padded dv, padded h)``. Equal widths are
    :func:`_head_blocks`'s layout. Unequal ones take the fewest heads a block
    whose q/k lanes and v lanes both fill whole lane rows, and pad nothing;
    None where no such count divides the heads (the XLA tiers serve)."""
    if d == dv:
        per, dp, hp = _head_blocks(h, d)
        return per, dp, dp, hp
    for per in (1, 2, 4, 8):
        if h % per == 0 and not (per * d % _LANES or per * dv % _LANES):
            return per, d, dv, h
    return None


def _to_blocks(x, dp: int, hp: int):
    """``[B, S, H, D]`` -> ``[B, S, hp*dp]`` (zero-padded where d or h grow)."""
    b, s, h, d = x.shape
    if (dp, hp) != (d, h):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, hp - h), (0, dp - d)))
    return x.reshape(b, s, hp * dp)


def _from_blocks(x, h: int, d: int, dp: int):
    b, s, width = x.shape
    return x.reshape(b, s, width // dp, dp)[:, :, :h, :d]


def _rows(x, per: int, hp: int):
    """``[B, H, Sq]`` f32 rows -> ``[B, hp // per, per, Sq]``, a head block's
    rows together (heads padded like the operands')."""
    b, h, sq = x.shape
    if hp != h:
        x = jnp.pad(x, ((0, 0), (0, hp - h), (0, 0)))
    return x.reshape(b, hp // per, per, sq)


def _specs(per: int, dp: int, dvp: int, bq: int, bk: int, q_inner: bool):
    """Block specs of the q-side tiles (q, dq at ``dp`` a head; o, dO at
    ``dvp``), their f32 rows and the k-side tiles (k, dk; v, dv) for a grid
    (batch, head block, outer, inner), the q blocks inner or not."""
    qi, ki = (3, 2) if q_inner else (2, 3)      # their grid dimensions
    tile = lambda rows, wide, at: pl.BlockSpec(                 # noqa: E731
        (None, rows, per * wide), lambda *g: (g[0], g[at], g[1]),
        memory_space=pltpu.VMEM)
    qrow = pl.BlockSpec((None, None, per, bq),
                        lambda *g: (g[0], g[1], 0, g[qi]),
                        memory_space=pltpu.VMEM)
    return (tile(bq, dp, qi), tile(bq, dvp, qi), qrow, tile(bk, dp, ki),
            tile(bk, dvp, ki))


@functools.partial(jax.jit, static_argnums=tuple(range(3, 12)))
def _flash_forward(q, k, v, causal, q_offset, k_offset, sm_scale, block_q,
                   block_k, interpret, k_valid=None, sub_k=None):
    """q [B,Sq,H,D], k [B,Sk,H,D], v [B,Sk,H,Dv] -> (out [B,Sq,H,Dv], lse
    [B,H,Sq] f32, the backward residual)."""
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[-1]
    bq, bk, sub_k = _resolve_blocks(sq, sk, block_q, block_k, sub_k)
    per, dp, dvp, hp = _require_layout(h, d, dv)
    kernel = functools.partial(
        _flash_kernel, heads=per, head_dim=dp, v_dim=dvp, block_q=bq,
        block_k=bk, sub_k=sub_k, causal=causal, q_offset=q_offset,
        k_offset=k_offset, sm_scale=sm_scale, k_valid=k_valid)
    qspec, ospec, qrow, kspec, vspec = _specs(per, dp, dvp, bq, bk,
                                              q_inner=False)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, hp // per, sq // bq, sk // bk),  # k innermost: scratch carries
        in_specs=[qspec, kspec, vspec],
        out_specs=[ospec, qrow],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hp * dvp), q.dtype),
            jax.ShapeDtypeStruct((b, hp // per, per, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((per, bq, _LANES), jnp.float32),
            pltpu.VMEM((per, bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, per * dvp), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_fwd",
    )(_to_blocks(q, dp, hp), _to_blocks(k, dp, hp), _to_blocks(v, dvp, hp))
    return _from_blocks(out, h, dv, dvp), lse.reshape(b, hp, sq)[:, :h]


def _require_layout(h: int, d: int, dv: int):
    layout = _head_layout(h, d, dv)
    if layout is None:
        raise NotImplementedError(
            f"{h} heads of {d} (q, k) and {dv} (v) lanes fill no whole lane "
            f"rows together; the XLA tiers take them")
    return layout


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_lse(q, k, v, causal, q_offset, k_offset, sm_scale, block_q,
               block_k, interpret, k_valid):
    """The kernels' differentiable entry, sequence-major: q [B,Sq,H,D], k/v
    [B,Sk,H,D] -> (out [B,Sq,H,D], lse [B,H,Sq] f32). Differentiable in both
    outputs (the lse cotangent folds into the score gradient as
    ``ds += p * g_lse``)."""
    return _flash_lse_fwd(q, k, v, causal, q_offset, k_offset, sm_scale,
                          block_q, block_k, interpret, k_valid)[0]


def _flash_lse_fwd(q, k, v, causal, q_offset, k_offset, sm_scale, block_q,
                   block_k, interpret, k_valid):
    out, lse = _partitioned_fwd(causal, q_offset, k_offset, sm_scale, block_q,
                                block_k, interpret, k_valid)(q, k, v)
    # a block rematerialised whole keeps both (models/lm.py saves the name),
    # so its backward pass runs no forward kernel a second time
    out = checkpoint_name(out, "attention_out")
    lse = checkpoint_name(lse, "attention_out")
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, q_offset, k_offset, sm_scale, block_q, block_k,
                   interpret, k_valid, residuals, gs):
    """``g_lse`` (the lse-output cotangent, [B,H,Sq]) folds into the score
    gradient: d lse_i / d s_ij = p_ij, so ds = p * (dp - D + g_lse) — carried
    by passing D' = D - g_lse through the unchanged kernels."""
    q, k, v, out, lse = residuals
    g, g_lse = gs
    # D_i = dO_i . O_i (the softmax-normalizer correction), cheap elementwise
    # — stays outside the partitioned call, GSPMD shards it fine.
    dvec = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dvec = dvec.transpose(0, 2, 1) - g_lse.astype(jnp.float32)
    return _partitioned_bwd(causal, q_offset, k_offset, sm_scale, block_q,
                            block_k, interpret, k_valid)(q, k, v, lse, g, dvec)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _swap_sh(x):
    """[B,H,S,D] <-> [B,S,H,D]."""
    return x.transpose(0, 2, 1, 3)


def flash_attention_lse(q, k, v, causal: bool = False, q_offset: int = 0,
                        k_offset: int = 0, sm_scale: float | None = None,
                        block_q: int | None = None, block_k: int | None = None,
                        interpret: bool | None = None,
                        k_valid: int | None = None):
    """Flash attention that also returns the per-row logsumexp.

    q [B,H,Sq,D], k/v [B,H,Sk,D] -> ``(out [B,H,Sq,D], lse [B,H,Sq] f32)`` with
    ``lse = logsumexp_k(q.k * sm_scale)`` over this call's (masked) keys. The
    residual a caller needs to softmax-combine partial attention over disjoint
    key sets — :func:`ddw_tpu.parallel.ring_attention.ring_attention` folds one
    of these per ring hop. ``q_offset``/``k_offset`` are the global positions
    of the local blocks (used by ring attention for causal masking across
    rotated K/V shards). ``k_valid`` (static) masks keys at global position >=
    it — the padded tail when Sk was padded to a block multiple (see
    :func:`flash_mha`). Differentiable in both outputs. The kernels work
    sequence-major; this entry transposes in and out."""
    sm_scale, interpret = _resolve_defaults(sm_scale, interpret, q.shape[-1])
    out, lse = _flash_lse(_swap_sh(q), _swap_sh(k), _swap_sh(v), causal,
                          q_offset, k_offset, sm_scale, block_q, block_k,
                          interpret, k_valid)
    return _swap_sh(out), lse


def flash_attention(q, k, v, causal: bool = False, q_offset: int = 0,
                    k_offset: int = 0, sm_scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None,
                    k_valid: int | None = None):
    """Flash attention: softmax(q k^T / sqrt(d)) v without materializing scores.

    q [B,H,Sq,D], k/v [B,H,Sk,D] -> [B,H,Sq,D]; the arguments of
    :func:`flash_attention_lse`, its first output."""
    return flash_attention_lse(q, k, v, causal, q_offset, k_offset, sm_scale,
                               block_q, block_k, interpret, k_valid)[0]


def _bwd_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref, *refs,
                heads: int, head_dim: int, v_dim: int, block_q: int,
                block_k: int, sub_k: int, causal: bool, q_offset: int,
                k_offset: int, sm_scale: float, k_valid: int | None,
                dq_home: str):
    """The whole backward pass of one (batch, head block, k-block, q-block)
    grid step, Q innermost: a score sub-block, its exponential and dS are made
    ONCE and all three gradients come of them.

    p_ij = exp(s_ij - L_i) rematerialized per sub-block from the saved
    logsumexp; ds_ij = p_ij * (dO_i . v_j - D_i); dv_j += p_ij^T dO_i; dk_j +=
    sm_scale * ds_ij^T q_i; dq_i += sm_scale * ds_ij k_j. Works on TRANSPOSED
    scores s^T = k q^T ``[sub_k, block_q]``: p^T and ds^T are then the left
    operands of plain matmuls for dV and dK (no score tile is ever transposed
    by hand), dQ contracts ds^T over its first dimension, and L and D
    broadcast along sublanes from their lane-dense rows. The S x S matrices
    exist only sub-block-wise in VMEM.

    dK and dV sum over the inner grid dimension in VMEM scratch. dQ sums over
    the OUTER one, so a q block's dQ is visited once a key block, and
    ``dq_home`` (:func:`_dq_home`) says where the float32 sum waits between
    visits. ``"vmem"``: a ``[Sq, lanes]`` scratch holding every q block of the
    (batch, head block) in hand; the last key block's steps scale, round and
    write it (the output block's index stays at 0 until then, so nothing
    unfinished is written back). ``"hbm"``: a float32 buffer of that shape in
    HBM that the kernel reads and writes by its own DMAs
    (:func:`_dq_through_hbm`), rounding into the output itself on a q block's
    last visit."""
    kj, qb = pl.program_id(2), pl.program_id(3)
    num_kb, num_qb = pl.num_programs(2), pl.num_programs(3)
    if dq_home == "vmem":
        dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, dq_scr = refs
    else:
        dk_ref, dv_ref, dq_hbm, spill, dk_scr, dv_scr, dq_scr, dq_in, \
            dq_out, sems, state = refs
    q_heads, v_heads = _qv_windows(heads, head_dim, dk_scr.shape[-1], v_dim,
                                   dv_scr.shape[-1])

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q0 = q_offset + qb * block_q
    k0 = k_offset + kj * block_k
    bounds = _sub_block_range(q0, k0, block_q, block_k, sub_k, causal, k_valid)

    def accumulate(dq_at):
        """Add this step's dK, dV to their scratch and its dQ to
        ``dq_scr[dq_at(lanes)]``."""
        for t, ((qw, qmask), (vw, vmask)) in enumerate(zip(q_heads, v_heads)):
            q = _only(qmask, q_ref[_at(qw)])
            do = _only(vmask, do_ref[_at(vw)])
            lse = _finite_ref(lse_ref[t:t + 1, :])           # [1, block_q]
            dvec = dvec_ref[t:t + 1, :]

            def _accum(j, masked):
                ks = pl.ds(pl.multiple_of(j * sub_k, sub_k), sub_k)
                k_blk = k_ref[_at(qw, ks)]
                st = _scores(k_blk, q, sm_scale)          # [sub_k, block_q]
                if masked:
                    st = _mask_scores(st, q0, k0 + j * sub_k, causal, k_valid,
                                      0)
                pt = jnp.exp(st - lse)
                dv = jnp.dot(pt.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
                dpt = _scores(v_ref[_at(vw, ks)], do, 1.0)
                dst = (pt * (dpt - dvec)).astype(q.dtype)
                dk = jnp.dot(dst, q, preferred_element_type=jnp.float32)
                dq = jax.lax.dot_general(dst, k_blk, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                dv_scr[_at(vw, ks)] += dv    # q and do carry this head's lanes
                dk_scr[_at(qw, ks)] += dk    # only, so dv and dk do too;
                dq_scr[dq_at(qw)] += (       # k's window holds a neighbour's
                    dq if qmask is None else jnp.where(qmask, dq, 0.0))

            _for_sub_blocks(*bounds, _accum)

    if dq_home == "vmem":
        rows = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)

        @pl.when(kj == 0)
        def _first_visit():
            dq_scr[rows, :] = jnp.zeros((block_q, dq_scr.shape[-1]),
                                        jnp.float32)

        accumulate(lambda lanes: _at(lanes, rows))

        @pl.when(kj == num_kb - 1)
        def _last_visit():
            dq_ref[...] = (sm_scale * dq_scr[rows, :]).astype(dq_ref.dtype)
    else:
        n_vis = bounds[1]
        last_kj = num_kb - 1        # the last key block this q block sees
        if causal:
            last_kj = jnp.clip((q0 + block_q - 1 - k_offset) // block_k, 0,
                               last_kj)
        _dq_through_hbm(dq_hbm, spill, dq_scr, dq_in, dq_out, sems, state,
                        accumulate, isinstance(n_vis, int) or n_vis > 0,
                        last_kj, block_q, sm_scale)

    @pl.when(qb == num_qb - 1)
    def _finalize():
        dk_ref[...] = (sm_scale * dk_scr[...]).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _dq_through_hbm(dq_hbm, spill, dq_scr, dq_in, dq_out, sems, state,
                    accumulate, visible, last_kj, block_q: int,
                    sm_scale: float):
    """The ``"hbm"`` home of :func:`_bwd_kernel`'s dQ. A step that sees any of
    its keys makes its sum in one of ``dq_scr``'s two slots and adds what the
    earlier key blocks left in ``spill`` (float32 ``[Sq, lanes]`` in HBM, one
    (batch, head block) at a time; read into ``dq_in`` while the step
    computes). At ``last_kj``, the last key block this q block sees, the sum
    is scaled, rounded into ``dq_out``'s slot and written to ``dq_hbm`` ``[B,
    Sq, lanes of every head block]``; before it, the slot goes back to
    ``spill``. Either write is in flight while the next step computes in the
    other slot. ``state`` (SMEM) holds, across the whole grid (which therefore
    runs in order): which kind of write is in flight (0 none, 1 to ``spill``,
    2 to ``dq_hbm``), how many steps have written, and the q block of a write
    to ``spill``, which a read of that block waits for. A step that sees no
    key does nothing, but that a q block no key block sees is written as
    zeros on the first."""
    pid = [pl.program_id(a) for a in range(4)]
    steps = [pl.num_programs(a) for a in range(4)]
    kj, qb = pid[2], pid[3]
    width = dq_scr.shape[-1]
    rows = pl.ds(pl.multiple_of(qb * block_q, block_q), block_q)
    out_window = dq_hbm.at[
        pid[0], rows, pl.ds(pl.multiple_of(pid[1] * width, _LANES), width)]

    def to_spill(slot):
        return pltpu.make_async_copy(dq_scr.at[slot], spill.at[rows],
                                     sems.at[1])

    def to_output(slot):
        return pltpu.make_async_copy(dq_out.at[slot], out_window, sems.at[1])

    def wait_for_write():
        pl.when(state[0] == 1)(to_spill(0).wait)
        pl.when(state[0] == 2)(to_output(0).wait)
        state[0] = 0

    @pl.when(functools.reduce(jnp.logical_and, [p == 0 for p in pid]))
    def _start():
        state[0] = 0
        state[1] = 0

    last = kj == last_kj

    def visit():
        slot = jax.lax.rem(state[1], 2)
        # whoever sees a key block sees the earlier ones: from the second on
        # the sum so far is in ``spill``
        earlier = kj > 0
        read = pltpu.make_async_copy(spill.at[rows], dq_in, sems.at[0])
        pl.when(jnp.logical_and(earlier, jnp.logical_and(
            state[0] == 1, state[2] == qb)))(wait_for_write)
        pl.when(earlier)(read.start)
        dq_scr[slot] = jnp.zeros((block_q, width), jnp.float32)
        accumulate(lambda lanes: (slot, slice(None),
                                  slice(None) if lanes is None else lanes))

        @pl.when(earlier)
        def _add_earlier():
            read.wait()
            dq_scr[slot] += dq_in[...]

        wait_for_write()            # the step before's, from the other slot

        @pl.when(last)
        def _round():
            dq_out[slot] = (sm_scale * dq_scr[slot]).astype(dq_out.dtype)
            to_output(slot).start()
            state[0] = 2

        @pl.when(jnp.logical_not(last))
        def _keep():
            to_spill(slot).start()
            state[0] = 1
            state[2] = qb

        state[1] = state[1] + 1

    if visible is True:
        visit()
    else:
        pl.when(jnp.logical_or(visible, last))(visit)

    pl.when(functools.reduce(
        jnp.logical_and, [p == n - 1 for p, n in zip(pid, steps)]))(
            wait_for_write)


# A q block's dQ is summed over the key blocks, the kernel's OUTER grid
# dimension. With one key block there is no sum; else the float32 sum of every
# q block of a (batch, head block) waits in VMEM while the kernel stays inside
# _VMEM_UNASKED with it (Mosaic's plan for a described v5e, bfloat16, blocks
# 512 x 1024 x 512: 5.7 MiB at two heads of 64 and S = 1,024; 9.7 MiB at one
# of 128 and S = 8,192 with 4 MiB of sum; 17.6 at 192 beside 128 and S =
# 4,096 with 6 of sum, 23.6 at S = 8,192 with 12), and above that in HBM
# (13.8 MiB at 192 beside 128, whatever S). What the trip through HBM costs
# the kernel alone (tools/fa2_sweep.py --preset blocks --dq-home vmem,hbm, one
# v5e chip, PR 45; ms in VMEM / through HBM): 23.59 / 23.72 at [2,32,8192,128],
# 4.574 / 4.689 at [1,32,4096,192|128], 32.93 / 33.32 at [2,32,8192,192|128].
_DQ_VMEM_MAX = 4 * 1024 * 1024


def _dq_home(sq: int, sk: int, block_k: int, q_lanes: int) -> str:
    """Where :func:`_bwd_kernel` keeps dQ's sum between a q block's visits,
    from the shapes alone: ``"vmem"`` or ``"hbm"``."""
    if sk == block_k or sq * q_lanes * 4 <= _DQ_VMEM_MAX:
        return "vmem"
    return "hbm"


@functools.partial(jax.jit, static_argnums=tuple(range(6, 15)))
def _flash_bwd(q, k, v, g, lse, dvec, causal, q_offset, k_offset, sm_scale,
               block_q, block_k, interpret, k_valid=None, sub_k=None):
    """q [B,Sq,H,D], g [B,Sq,H,Dv]; k [B,Sk,H,D], v [B,Sk,H,Dv]; lse, dvec
    [B,H,Sq] f32 -> (dq, dk, dv), one ``pallas_call``. It keeps the name the
    dK/dV kernel had, ``flash_dkv``: the benchmark finds the kernels by name."""
    b, sq, h, d = q.shape
    sk, dv_ = k.shape[1], v.shape[-1]
    bq, bk, sub_k = _resolve_blocks(sq, sk, block_q, block_k, sub_k)
    per, dp, dvp, hp = _require_layout(h, d, dv_)
    nq, nk, lanes = sq // bq, sk // bk, per * dp
    home = _dq_home(sq, sk, bk, lanes)
    qspec, ospec, qrow, kspec, vspec = _specs(per, dp, dvp, bq, bk,
                                              q_inner=True)
    scratch = [pltpu.VMEM((bk, lanes), jnp.float32),
               pltpu.VMEM((bk, per * dvp), jnp.float32)]
    dq_shapes = [jax.ShapeDtypeStruct((b, sq, hp * dp), q.dtype)]
    if home == "vmem":
        # the block index stays put until the last key block's steps write
        dq_specs = [pl.BlockSpec(
            (None, bq, lanes),
            lambda *g: (g[0], jnp.where(g[2] == nk - 1, g[3], 0), g[1]),
            memory_space=pltpu.VMEM)]
        scratch.append(pltpu.VMEM((sq, lanes), jnp.float32))
        semantics = ("parallel", "parallel", "arbitrary", "arbitrary")
    else:
        dq_shapes.append(jax.ShapeDtypeStruct((sq, lanes), jnp.float32))
        dq_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch += [pltpu.VMEM((2, bq, lanes), jnp.float32),
                    pltpu.VMEM((bq, lanes), jnp.float32),
                    pltpu.VMEM((2, bq, lanes), q.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SMEM((3,), jnp.int32)]
        semantics = ("arbitrary",) * 4      # ``state`` runs through them all
    dk, dv, dq, *_ = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=per, head_dim=dp, v_dim=dvp,
                          block_q=bq, block_k=bk, sub_k=sub_k, causal=causal,
                          q_offset=q_offset, k_offset=k_offset,
                          sm_scale=sm_scale, k_valid=k_valid, dq_home=home),
        grid=(b, hp // per, nk, nq),
        in_specs=[kspec, vspec, qspec, ospec, qrow, qrow],
        out_specs=[kspec, vspec, *dq_specs],
        out_shape=[jax.ShapeDtypeStruct((b, sk, hp * dp), k.dtype),
                   jax.ShapeDtypeStruct((b, sk, hp * dvp), v.dtype),
                   *dq_shapes],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_dkv",
    )(_to_blocks(k, dp, hp), _to_blocks(v, dvp, hp), _to_blocks(q, dp, hp),
      _to_blocks(g, dvp, hp), _rows(lse, per, hp), _rows(dvec, per, hp))
    return (_from_blocks(dq, h, d, dp), _from_blocks(dk, h, d, dp),
            _from_blocks(dv, h, dv_, dvp))


@functools.lru_cache(maxsize=None)
def _partitioned_bwd(causal, q_offset, k_offset, sm_scale, block_q, block_k,
                     interpret, k_valid):
    """(q, k, v, lse, g, dvec) -> (dq, dk, dv), batch/head-partitioned.

    Pallas FA2 backward in one kernel over the saved logsumexp — O(S) memory,
    the S x S matrices never leave VMEM. ``lse`` and ``dvec`` arrive as
    [B,H,Sq] so every operand has the batch and heads dims the partition rule
    shards."""

    def impl(q, k, v, lse, g, dvec):
        return _flash_bwd(q, k, v, g, lse, dvec, causal, q_offset, k_offset,
                          sm_scale, block_q, block_k, interpret, k_valid)

    fn = custom_partitioning(impl)
    return _def_bh_partition(
        fn, impl,
        "b q h d, b s h d, b s h e, b h q, b q h e, b h q -> "
        "b q h d, b s h d, b s h e",
        out_ndims=(4, 4, 4))


# Block caps, from tools/fa2_sweep.py --preset blocks on one TPU v5e chip (PR 26;
# [8,16,1024,64] bf16 causal; ms for forward / dQ / dK,dV at (block_q, block_k,
# sub_k)): (512,1024,512) 0.57 / 0.73 / 0.84 — the fastest of 18 for each of the
# three kernels; (512,512,512) 0.61 / 0.79 / 0.89; (256,1024,512) 0.63 / 0.82 /
# 1.05; (1024,1024,512) 0.68 / 0.83 / 0.99; (512,1024,256) 0.73 / 0.85 / 0.89;
# (512,1024,128) 1.09 / 1.06 / 1.04; (256,512,128) 1.44 / 1.37 / 1.63. The
# parent's kernels ([B,H,S,D] operands, 128 x 128 blocks, (block, 1) statistics)
# took 10.10 ms for the three together. Wide sub-blocks win (fewer loop steps,
# longer MXU runs) until the causal work they cannot skip outweighs it; q
# blocks of 512 keep two per head at S = 1024, so the upper-right key sub-block
# of the first is skipped.
# The same preset with the one-pass backward (PR 45; ms for forward / backward;
# the dQ and dK/dV kernels it replaced took 0.731 + 0.839 at the chosen
# blocks): (512,1024,512) 0.572 / 1.015 — again the fastest of 18 for both;
# (512,512,512) 0.610 / 1.075; (256,1024,512) 0.632 / 1.244; (1024,1024,512)
# 0.679 / 1.208; (512,1024,256) 0.732 / 1.160; (512,1024,128) 1.088 / 1.772;
# (256,512,128) 1.437 / 2.434. And at latent attention's widths, q/k heads of
# 192 beside v heads of 128, [1,32,4096,192|128] (the pair: 3.427 + 3.549):
# (512,1024,512) 2.433 / 4.689 — the fastest backward of 18 and the fastest
# sum; (1024,1024,512) 2.393 / 4.985, the fastest forward by 1.7 %;
# (1024,512,512) 2.439 / 5.518; (512,512,512) 2.545 / 5.229; (256,1024,512)
# 3.013 / 4.909; (512,1024,256) 2.942 / 4.853; (512,1024,128) 3.877 / 5.340;
# (256,512,128) 5.711 / 8.029. The caps hold for every layout.
_BLOCK_Q_MAX = 512
_BLOCK_K_MAX = 1024
_SUB_K_MAX = 512


def _pick_block(s: int, block: int | None, cap: int) -> int:
    """The block for a sequence of length ``s``: a multiple of 128 — it is the
    lane dim of the kernels' score tiles and of the logsumexp rows, and the
    sublane dim of the q/k/v tiles — so ``s`` is padded UP to a block multiple
    rather than the block shrunk to ``s`` (a block of exactly s=100 lowers in
    interpret mode but fails Mosaic tiling on real TPU). ``block=None`` is the
    code's own choice: the fewest blocks of at most ``cap`` rows, sized to
    pad least. An explicit ``block`` (tests, the sweep tool) still clamps to a
    short sequence."""
    aligned = -(-max(s, 1) // _LANES) * _LANES
    if block is not None:
        return max(_LANES, min(block, aligned) // _LANES * _LANES)
    n_blocks = -(-aligned // cap)
    return -(-aligned // (n_blocks * _LANES)) * _LANES


def _pick_sub_block(block_k: int) -> int:
    """The widest multiple of 128 up to ``_SUB_K_MAX`` that divides the block."""
    return max(c for c in range(_LANES, min(block_k, _SUB_K_MAX) + 1, _LANES)
               if block_k % c == 0)


def _resolve_blocks(sq: int, sk: int, block_q, block_k, sub_k):
    block_q = _pick_block(sq, block_q, _BLOCK_Q_MAX)
    block_k = _pick_block(sk, block_k, _BLOCK_K_MAX)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lengths ({sq},{sk}) must divide blocks "
                         f"({block_q},{block_k}); flash_mha pads")
    return block_q, block_k, sub_k or _pick_sub_block(block_k)


def _pad_seq(x, mult):
    """Zero-pad the sequence axis (dim 1 of [B,S,H,D]) up to a multiple."""
    pad = (-x.shape[1]) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))


# ---------------------------------------------------------------------------
# Short sequences: one block. Below _FLASH_MIN_SEQ a head's whole key range
# fits one VMEM tile, so nothing is streamed: a grid step holds the whole
# [Sq, 128] q tile and [Sk, 128] k and v tiles of a head block for a few batch
# rows, the softmax is a plain one (no running max, no rescale, no scratch
# carried between steps), and ONE backward kernel recomputes p once and gives
# dQ, dK and dV (as the streaming backward does since PR 45) — with the D =
# rowsum(dO . O) reduction inside it. A sequence that is no multiple
# of 128 is not padded in HBM: the block over-runs the array (Pallas reads the
# boundary block and drops the writes past the edge), the rows past the edge
# are undefined — NaN in the interpreter — and are replaced by zeros with a
# select before anything can multiply them, and the keys past the edge are
# masked like the streaming kernels' padded tail. Same arithmetic contract:
# matmuls in the input dtype with float32 accumulation, softmax in float32,
# lse lane-dense.
# ---------------------------------------------------------------------------

_SHORT_MAX_SEQ = 512        # the longest padded side one block takes
_SHORT_IMAGES_MAX = 8       # batch rows a grid step, at most (the sweep)
_SHORT_STEP_BYTES = 1024 * 1024     # ... and of q-tile bytes a grid step


def _short_pad(s: int, align: int) -> int:
    """The block's rows for a side of ``s``. The q side is the lane dim of the
    backward's score tile and of the lse rows: a multiple of 128. The key
    side is a sublane dim of the operand tiles and of the backward's score
    tile and the lane dim of the forward's, which Mosaic takes at any
    multiple of 16: 208 rows for 196 keys, not 256."""
    return -(-s // align) * align


def _pick_images(b: int, rows: int, width: int, itemsize: int) -> int:
    """Batch rows a grid step of the one-block kernels: a grid step's work is
    well under a microsecond of MXU time against a fixed cost of a few tenths
    of one, so a step takes several — the largest divisor of ``b`` up to
    ``_SHORT_IMAGES_MAX`` whose q tiles stay under ``_SHORT_STEP_BYTES`` (the
    backward holds eight such tiles, double-buffered)."""
    cap = max(1, min(_SHORT_IMAGES_MAX,
                     _SHORT_STEP_BYTES // (rows * width * itemsize)))
    return max(n for n in range(1, cap + 1) if b % n == 0)


def _valid_rows(x, n_valid: int):
    """``x`` with the rows from ``n_valid`` on replaced by zeros — a select,
    not a multiply: rows past the array's edge are undefined and may be NaN."""
    if n_valid == x.shape[0]:
        return x
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < n_valid, x, jnp.zeros_like(x))


def _short_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, heads: int,
                      head_dim: int, sq: int, sk: int, causal: bool,
                      sm_scale: float):
    """One (batch rows, head block) grid step: whole-sequence attention of
    each batch row of the step and each head of the lane block in turn."""
    images, sqp, width = q_ref.shape
    skp = k_ref.shape[1]
    masks = _head_masks(heads, head_dim, width)
    k_valid = sk if sk != skp else None

    def image(n, _):
        q = _valid_rows(q_ref[n], sq)
        k = _valid_rows(k_ref[n], sk)
        v = _valid_rows(v_ref[n], sk)
        out = None
        for t, mask in enumerate(masks):
            s = _scores(_only(mask, q), k, sm_scale)         # [sqp, skp]
            if causal or k_valid is not None:
                s = _mask_scores(s, 0, 0, causal, k_valid, 1)
            # every row sees key 0, so no row is fully masked: m is finite
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            o = jnp.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32) * (1.0 / l)
            out = o if out is None else jnp.where(mask, o, out)
            lse_ref[n, t:t + 1, :] = jnp.broadcast_to(
                m + jnp.log(l), (sqp, _LANES)).T[:1]
        o_ref[n] = out.astype(o_ref.dtype)

    jax.lax.fori_loop(0, images, image, None)


def _short_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, glse_ref,
                      dq_ref, dk_ref, dv_ref, *, heads: int, head_dim: int,
                      sq: int, sk: int, causal: bool, sm_scale: float):
    """The whole backward of one (batch rows, head block) grid step, on
    TRANSPOSED scores s^T = k q^T ``[skp, sqp]`` like the streaming one: lse,
    D and the lse cotangent broadcast along sublanes from lane-dense rows, p^T
    and ds^T are the left operands of plain matmuls for dV and dK, and dQ
    contracts ds^T over its first dimension. p is recomputed once."""
    images, sqp, width = q_ref.shape
    masks = _head_masks(heads, head_dim, width)
    k_valid = sk if sk != k_ref.shape[1] else None
    # D_i = dO_i . O_i of each head as one matmul: row t of ``ones`` holds
    # head t's lanes, so (ones (dO . O)^T)[t] is that head's D as a row
    shape = (-(-heads // 8) * 8, width)
    ones = jnp.where(jax.lax.broadcasted_iota(jnp.int32, shape, 1) // head_dim
                     == jax.lax.broadcasted_iota(jnp.int32, shape, 0),
                     1.0, 0.0)

    def image(n, _):
        q = _valid_rows(q_ref[n], sq)
        do = _valid_rows(do_ref[n], sq)
        o = _valid_rows(o_ref[n], sq)
        k = _valid_rows(k_ref[n], sk)
        v = _valid_rows(v_ref[n], sk)
        dvec = jax.lax.dot_general(
            ones, do.astype(jnp.float32) * o.astype(jnp.float32),
            (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)              # [8, sqp]
        dq = dk = dv = 0.0
        for t, mask in enumerate(masks):
            qm, dom = _only(mask, q), _only(mask, do)
            st = _scores(k, qm, sm_scale)                    # [skp, sqp]
            if causal or k_valid is not None:
                st = _mask_scores(st, 0, 0, causal, k_valid, 0)
            pt = jnp.exp(st - lse_ref[n, t:t + 1, :])
            dpt = _scores(v, dom, 1.0)
            # the lse cotangent folds in as D' = D - g_lse (see _flash_lse_bwd)
            dst = (pt * (dpt - (dvec[t:t + 1] - glse_ref[n, t:t + 1, :]))
                   ).astype(q.dtype)
            # qm, dom and the masked k carry this head's lanes only, so the
            # three products do too and the heads add up
            dv += jnp.dot(pt.astype(do.dtype), dom,
                          preferred_element_type=jnp.float32)
            dk += jnp.dot(dst, qm, preferred_element_type=jnp.float32)
            dq += jax.lax.dot_general(dst, _only(mask, k),
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dq_ref[n] = (sm_scale * dq).astype(dq_ref.dtype)
        dk_ref[n] = (sm_scale * dk).astype(dk_ref.dtype)
        dv_ref[n] = dv.astype(dv_ref.dtype)

    jax.lax.fori_loop(0, images, image, None)


def _short_specs(b, sq, sk, h, d, itemsize, images):
    """Grid, block specs (q side, k side, f32 rows) and head layout of the
    one-block kernels for q [b,sq,h,d] and k/v [b,sk,h,d]."""
    sqp, skp = _short_pad(sq, _LANES), _short_pad(sk, 16)
    if max(sqp, skp) > _SHORT_MAX_SEQ:
        raise ValueError(f"seq lengths ({sq},{sk}) do not fit one block of "
                         f"{_SHORT_MAX_SEQ}; the streaming kernels take them")
    per, dp, hp = _head_blocks(h, d)
    nb = images or _pick_images(b, max(sqp, skp), per * dp, itemsize)
    tile = lambda rows: pl.BlockSpec(                            # noqa: E731
        (nb, rows, per * dp), lambda i, j: (i, 0, j), memory_space=pltpu.VMEM)
    rows = pl.BlockSpec((nb, None, per, sqp), lambda i, j: (i, j, 0, 0),
                        memory_space=pltpu.VMEM)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT)
    return ((b // nb, hp // per), tile(sqp), tile(skp), rows, params,
            (per, dp, hp, sqp))


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _short_forward(q, k, v, causal, sm_scale, interpret, images=None):
    """q [B,Sq,H,D], k/v [B,Sk,H,D], both sides at most _SHORT_MAX_SEQ ->
    (out [B,Sq,H,D], lse [B,H,Sq] f32)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    grid, qspec, kspec, rows, params, (per, dp, hp, sqp) = _short_specs(
        b, sq, sk, h, d, q.dtype.itemsize, images)
    out, lse = pl.pallas_call(
        functools.partial(_short_fwd_kernel, heads=per, head_dim=dp, sq=sq,
                          sk=sk, causal=causal, sm_scale=sm_scale),
        grid=grid,
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, rows],
        out_shape=[jax.ShapeDtypeStruct((b, sq, hp * dp), q.dtype),
                   jax.ShapeDtypeStruct((b, hp // per, per, sqp),
                                        jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_short_fwd",
    )(_to_blocks(q, dp, hp), _to_blocks(k, dp, hp), _to_blocks(v, dp, hp))
    return _from_blocks(out, h, d, dp), lse.reshape(b, hp, sqp)[:, :h, :sq]


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _short_backward(q, k, v, out, lse, g, g_lse, causal, sm_scale, interpret,
                    images=None):
    """The operands and the outputs of :func:`_short_forward`, and their
    cotangents g [B,Sq,H,D], g_lse [B,H,Sq] -> (dq, dk, dv)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    grid, qspec, kspec, rows, params, (per, dp, hp, sqp) = _short_specs(
        b, sq, sk, h, d, q.dtype.itemsize, images)

    def padded_rows(x):         # [B,H,Sq] -> [B, hp//per, per, sqp], zeros
        return _rows(jnp.pad(x, ((0, 0), (0, 0), (0, sqp - sq))), per, hp)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_short_bwd_kernel, heads=per, head_dim=dp, sq=sq,
                          sk=sk, causal=causal, sm_scale=sm_scale),
        grid=grid,
        in_specs=[qspec, kspec, kspec, qspec, qspec, rows, rows],
        out_specs=[qspec, kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((b, sq, hp * dp), q.dtype),
                   jax.ShapeDtypeStruct((b, sk, hp * dp), k.dtype),
                   jax.ShapeDtypeStruct((b, sk, hp * dp), v.dtype)],
        compiler_params=params,
        interpret=interpret,
        name="flash_short_bwd",
    )(_to_blocks(q, dp, hp), _to_blocks(k, dp, hp), _to_blocks(v, dp, hp),
      _to_blocks(out, dp, hp), _to_blocks(g, dp, hp), padded_rows(lse),
      padded_rows(g_lse.astype(jnp.float32)))
    return tuple(_from_blocks(x, h, d, dp) for x in (dq, dk, dv))


@functools.lru_cache(maxsize=None)
def _partitioned_short(causal, sm_scale, interpret):
    """The one-block forward and backward, batch/head-partitioned like the
    streaming kernels (:func:`_def_bh_partition`)."""

    def fwd(q, k, v):
        return _short_forward(q, k, v, causal, sm_scale, interpret)

    def bwd(q, k, v, out, lse, g, g_lse):
        return _short_backward(q, k, v, out, lse, g, g_lse, causal, sm_scale,
                               interpret)

    return (_def_bh_partition(
                custom_partitioning(fwd), fwd,
                "b q h d, b s h d, b s h d -> b q h d, b h q",
                out_ndims=(4, 3)),
            _def_bh_partition(
                custom_partitioning(bwd), bwd,
                "b q h d, b s h d, b s h d, b q h d, b h q, b q h d, b h q -> "
                "b q h d, b s h d, b s h d", out_ndims=(4, 4, 4)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_short(q, k, v, causal, sm_scale, interpret):
    """The one-block kernels' differentiable entry: :func:`_flash_lse`'s
    contract (sequence-major operands, both outputs differentiable) for
    sequences of at most _SHORT_MAX_SEQ, keys from position 0."""
    return _flash_short_fwd(q, k, v, causal, sm_scale, interpret)[0]


def _flash_short_fwd(q, k, v, causal, sm_scale, interpret):
    out, lse = _partitioned_short(causal, sm_scale, interpret)[0](q, k, v)
    return (out, lse), (q, k, v, out, lse)


def _flash_short_bwd(causal, sm_scale, interpret, residuals, gs):
    return _partitioned_short(causal, sm_scale, interpret)[1](*residuals, *gs)


_flash_short.defvjp(_flash_short_fwd, _flash_short_bwd)


# ---------------------------------------------------------------------------
# Dispatch. Three tiers compute the same attention: plain XLA (one fused
# einsum chain, S² scores through HBM, saved for the backward), jax.checkpoint
# XLA (O(S) residuals, the S² tensors transient in the backward) and the Pallas
# flash kernels (scores never leave VMEM), in a streaming form and a one-block
# form for short sequences. What the kernels' advantage depends
# on is the sequence length: the XLA tiers make memory-bound passes over
# B*H*Sq*Sk float32 scores, the kernels do the same arithmetic from VMEM, and
# batch and heads scale both sides alike. Measured on one TPU v5e chip
# (tools/fa2_sweep.py --preset cells,ladder [--dim 128], PR 26; forward +
# backward, bf16, 16 heads, B*S = 8192 tokens; ms for xla / xla_ckpt / pallas):
#
#      S    D = 64, causal          D = 64, not causal      D = 128, causal
#    256    1.64 /  2.18 / 1.35     1.64 /  2.18 / 0.99     1.88 /  2.73 / 2.21
#    512    3.51 /  4.64 / 1.39     3.50 /  4.64 / 1.15     3.67 /  5.11 / 2.15
#   1024    6.88 /  9.27 / 1.87     6.87 /  9.27 / 2.30     7.07 /  9.65 / 2.69
#   2048   13.47 / 18.03 / 2.98    13.45 / 17.98 / 4.31    13.79 / 18.47 / 4.15
#   4096   26.69 / 35.49 / 5.11    26.72 / 35.49 / 8.22    27.55 / 36.24 / 6.54
#   ViT-B/16's [128,12,196,64], not causal, [B,H,S,D] operands: 4.62 / 6.39 /
#   4.57 (196 pads to 256, and the kernels' layout costs two transposes each way)
#
# (S = 1024, D = 64, causal is the GPT-2 medium cells' shape.) So sequences of
# _FLASH_MIN_SEQ and more go to the streaming kernels: 2.5x at 512 and 5.2x at
# 4096 at D = 64, 1.7x and 4.2x at D = 128.
#
# Below that the whole key range fits one block, and the one-block kernels
# ("Short sequences" above; tools/fa2_sweep.py --preset short [--dim 128], PR
# 29, same chip, forward + backward, bf16, 16 heads, B*S = 8192 tokens,
# operands [B,S,H,D]; ms for xla / xla_ckpt / streaming kernels / one block;
# not causal, causal where it differs by more than 0.01):
#
#      S    D = 64                                    D = 128
#     32    1.38 / 1.79 / 7.88 / 2.31                 1.07 / 1.23 / 12.39 / 4.57
#     64    1.30 / 1.75 / 3.74 / 1.28                 1.07 / 1.24 /  5.17 / 2.61
#    128    0.56 / 0.69 / 1.07 (1.60) / 0.74          0.99 / 1.29 /  2.42 (2.65) / 1.63
#    196    1.68 / 2.34 / 1.86 / 0.96                 1.97 / 2.57 /  2.61 / 1.54
#    256    1.64 / 2.18 / 0.99 (1.35) / 0.77          1.88 / 2.71 /  1.98 (2.21) / 1.37
#    384    2.51 / 3.16 / 1.05 (1.41) / 0.84 (0.82)   2.65 / 3.81 /  1.95 (2.18) / 1.43
#    512    3.51 / 4.64 / 1.15 (1.39) / 0.95          3.67 / 5.11 /  1.90 (2.15) / 1.45
#   ViT-B/16's [128,12,196,64], not causal: 4.67 / 6.45 / 4.58 / 2.23 with
#   [B,S,H,D] operands, 4.62 / 6.39 / 4.57 / 2.30 with [B,H,S,D] ones (two
#   transposes each way); the one-block forward / backward alone by batch
#   rows a grid step: 1.104 / 1.806 at one, 1.119 / 1.766 at four, 1.114 /
#   1.752 at eight (the code's choice), 1.110 / 1.746 at sixteen — the grid
#   step's fixed cost is not what bounds them. With the key side padded to 16
#   and not to 128 (208 rows for 196 keys, as the code does) 1.097 / 1.654
#   alone, and in ViT's traced step a layer's forward 0.84 -> 0.83 ms and its
#   backward 0.91 -> 0.81. A forward on transposed scores (reductions along
#   sublanes, a transposed-LHS matmul for P V) lost: 1.398.
#
# The XLA tier jumps between S = 128 (0.56 ms, an aligned length) and S = 196
# (1.68 ms for one and a half times the scores); the one-block kernels win from
# 196 up at both head dims — 1.75x, 2.1x, 3.0x at D = 64 and 1.28x, 1.37x, 1.85x
# at D = 128 for S = 196, 256, 384 — tie at 64 and lose at 128 and below, where
# a sequence pads to a 128-row block and the call is overhead-bound. So "auto"
# sends min(Sq, Sk) >= _SHORT_MIN_SEQ with max(Sq, Sk) <= _SHORT_MAX_SEQ (and
# not both sides at _FLASH_MIN_SEQ) to them at the two head dims measured;
# nothing between 128 and 196 was measured, so the edge sits just under 196.
# At S = 512 the one-block form also beats the streaming kernels (0.95 against
# 1.15 and 1.39 ms), but _FLASH_MIN_SEQ stays: the LM's shapes were not what
# PR 29 measured end to end. Everything else below the crossover keeps the XLA
# tiers, the score footprint choosing between them: plain while the saved S²
# tensors are small, checkpointed above that, and the streaming kernels again
# where even the transient S² tensor is memory-infeasible.
# ---------------------------------------------------------------------------

_FLASH_MIN_SEQ = 512
_SHORT_MIN_SEQ = 192
_SHORT_HEAD_DIMS = (64, 128)

# Score-matrix bytes (B*H*Sq*Sk*4, f32) thresholds; env-overridable for tuning.
_XLA_PLAIN_MAX = int(os.environ.get("DDW_ATTN_XLA_PLAIN_MAX", 256 * 1024**2))
_XLA_CKPT_MAX = int(os.environ.get("DDW_ATTN_XLA_CKPT_MAX", 2 * 1024**3))


def _xla_attention_lse(q, k, v, causal: bool, q_offset, k_offset,
                       sm_scale: float, k_valid: int | None):
    """Reference-semantics attention via one fused XLA einsum chain.

    Matches the Pallas kernels' contract exactly: matmuls run in the input
    dtype (bf16 -> full MXU rate) with f32 accumulation
    (``preferred_element_type``, same as the kernels' ``jnp.dot``), softmax
    bookkeeping in f32, global causal offsets, ``k_valid`` key masking, and an
    lse output for ring combination. Autodiff gives the backward; XLA fuses
    mask+softmax into the matmuls."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    sq, sk = q.shape[2], k.shape[2]
    kpos = k_offset + jnp.arange(sk)
    mask = None
    if causal:
        qpos = q_offset + jnp.arange(sq)
        mask = kpos[None, :] <= qpos[:, None]
    if k_valid is not None:
        kv_mask = (kpos < k_valid)[None, :]
        mask = kv_mask if mask is None else (mask & kv_mask)
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e30)  # fully-masked rows: keep exp finite
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = (jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v,
                      preferred_element_type=jnp.float32)
           / jnp.maximum(l, 1e-30)).astype(q.dtype)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return out, lse


def _attn_impl(q, k, impl: str, v_dim: int | None = None) -> str:
    """The tier for q [B,H,Sq,D] and k [B,H,Sk,D] (shapes are all it reads),
    and v heads of ``v_dim`` where they differ from D: the streaming kernels
    take those where a lane block holds whole heads of both
    (:func:`_head_layout`), the XLA tiers everything else."""
    if impl != "auto":
        return impl
    b, h, sq, d = q.shape
    sk = k.shape[2]
    equal = v_dim in (None, d)
    kernels = equal or _head_layout(h, d, v_dim) is not None
    if min(sq, sk) >= _FLASH_MIN_SEQ and kernels:
        return "pallas"
    if (min(sq, sk) >= _SHORT_MIN_SEQ and max(sq, sk) <= _SHORT_MAX_SEQ
            and d in _SHORT_HEAD_DIMS and equal):
        return "pallas_short"
    score_bytes = b * h * sq * sk * 4
    if score_bytes <= _XLA_PLAIN_MAX:
        return "xla"
    if score_bytes <= _XLA_CKPT_MAX or not kernels:
        return "xla_ckpt"
    return "pallas"


def flash_mha(q, k, v, causal: bool = False, sm_scale: float | None = None,
              block_q: int | None = None, block_k: int | None = None,
              interpret: bool | None = None, impl: str = "auto") -> jnp.ndarray:
    """Attention for arbitrary sequence lengths (the model-facing entry),
    q [B,H,Sq,D], k/v [B,H,Sk,D] -> [B,H,Sq,D].

    ``impl``: ``auto`` (dispatch on the shape, see the comment above
    ``_attn_impl``), ``xla``, ``xla_ckpt`` (rematerialized backward),
    ``pallas`` (the streaming flash kernels, any length: pads Sq/Sk to block
    multiples in HBM, masks padded keys via ``k_valid``, slices padded query
    rows back off) or ``pallas_short`` (the one-block kernels, both sides at
    most 512: nothing padded in HBM). This entry transposes in and out of
    the kernels' layout; :func:`flash_mha_seq_major` does not."""
    return flash_mha_lse(q, k, v, causal, sm_scale, block_q, block_k,
                         interpret, impl)[0]


def flash_mha_lse(q, k, v, causal: bool = False, sm_scale: float | None = None,
                  block_q: int | None = None, block_k: int | None = None,
                  interpret: bool | None = None, impl: str = "auto"):
    """Padded-length attention with logsumexp — ``(out, lse [B,H,Sq])``.

    Same dispatch and padding contract as :func:`flash_mha`; the lse rows for
    padded queries are sliced off with the outputs. Ring attention calls this
    per hop so arbitrary local shard lengths work."""
    with jax.named_scope("attention"):      # every tier, for a profile's split
        return _dispatch_lse(q, k, v, causal, sm_scale, block_q, block_k,
                             interpret, _attn_impl(q, k, impl, v.shape[-1]),
                             seq_major=False)


def flash_mha_seq_major(q, k, v, causal: bool = False,
                        sm_scale: float | None = None,
                        impl: str = "auto") -> jnp.ndarray:
    """:func:`flash_mha` for operands as the projections produce them (the LM
    and ViT): q [B,Sq,H,D], k/v [B,Sk,H,D] -> [B,Sq,H,D]. The kernels take
    that layout as it is; the XLA tiers get the ``[B,H,S,D]`` transposes they
    always got. ``v`` may be ``[B,Sk,H,Dv]`` with ``Dv != D``; the output is
    then ``[B,Sq,H,Dv]``."""
    with jax.named_scope("attention"):
        (b, sq, h, d), sk = q.shape, k.shape[1]
        tier = _attn_impl(jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
                          jax.ShapeDtypeStruct((b, h, sk, d), k.dtype), impl,
                          v.shape[-1])
        return _dispatch_lse(q, k, v, causal, sm_scale, None, None, None,
                             tier, seq_major=True)[0]


def _dispatch_lse(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                  chosen, seq_major):
    scale, interpret = _resolve_defaults(sm_scale, interpret, q.shape[-1])
    if chosen in ("xla", "xla_ckpt"):
        fn = functools.partial(_xla_attention_lse, causal=causal, q_offset=0,
                               k_offset=0, sm_scale=scale, k_valid=None)
        if chosen == "xla_ckpt":
            fn = jax.checkpoint(fn)
        if not seq_major:
            return fn(q, k, v)
        out, lse = fn(_swap_sh(q), _swap_sh(k), _swap_sh(v))
        return _swap_sh(out), lse
    if not seq_major:
        q, k, v = _swap_sh(q), _swap_sh(k), _swap_sh(v)
    sq, sk = q.shape[1], k.shape[1]
    if chosen == "pallas_short":
        out, lse = _flash_short(q, k, v, causal, scale, interpret)
        return (out if seq_major else _swap_sh(out)), lse
    bq = _pick_block(sq, block_q, _BLOCK_Q_MAX)
    bk = _pick_block(sk, block_k, _BLOCK_K_MAX)
    kp = _pad_seq(k, bk)
    out, lse = _flash_lse(_pad_seq(q, bq), kp, _pad_seq(v, bk), causal, 0, 0,
                          scale, bq, bk, interpret,
                          sk if kp.shape[1] != sk else None)
    out, lse = out[:, :sq], lse[:, :, :sq]
    return (out if seq_major else _swap_sh(out)), lse
