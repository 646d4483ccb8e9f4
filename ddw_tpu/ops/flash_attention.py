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
- backward pass as two Pallas kernels (FA2 schedule): the forward saves the
  per-row logsumexp; dQ streams K/V blocks, dK/dV streams Q/dO blocks, each
  rematerializing p = exp(s - L) blockwise in VMEM — O(S) HBM for the whole
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
    forward and both backward kernels so the masking can never desynchronize.
    Keys run along ``k_axis`` of ``sc`` (1 in the forward and dQ kernels, 0 in
    the dK/dV kernel, which works on transposed scores). ``k_valid`` (static)
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
    masked rows stay at zero output and zero gradient. Load-bearing in all
    three kernels."""
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


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, dq_ref,
               dq_scr, lse_scr, dvec_scr, *, heads: int, head_dim: int,
               v_dim: int, block_q: int, block_k: int, sub_k: int,
               causal: bool,
               q_offset: int, k_offset: int, sm_scale: float,
               k_valid: int | None):
    """dQ pass (FA2 backward): grid (B, head blocks, q-blocks, k-blocks), K
    innermost.

    p_ij = exp(s_ij - L_i) rematerialized per sub-block from the saved
    logsumexp; ds_ij = p_ij * (dO_i . v_j - D_i); dq_i += sm_scale * ds_ij k_j.
    The S x S matrices exist only sub-block-wise in VMEM. L and D arrive as
    lane-dense rows and are turned once a q block into lane-replicated
    ``[block_q, 128]`` columns (sublane broadcast + one aligned transpose).
    """
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    num_kb = pl.num_programs(3)
    q_heads, v_heads = _qv_windows(heads, head_dim, dq_scr.shape[-1], v_dim,
                                   v_ref.shape[-1])

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        for t in range(heads):
            lse_scr[t] = jnp.broadcast_to(_finite_ref(lse_ref[t:t + 1, :]),
                                          (_LANES, block_q)).T
            dvec_scr[t] = jnp.broadcast_to(dvec_ref[t:t + 1, :],
                                           (_LANES, block_q)).T

    q0 = q_offset + qi * block_q
    k0 = k_offset + kb * block_k
    bounds = _sub_block_range(q0, k0, block_q, block_k, sub_k, causal, k_valid)

    for t, ((qw, qmask), (vw, vmask)) in enumerate(zip(q_heads, v_heads)):
        q = _only(qmask, q_ref[_at(qw)])
        do = _only(vmask, do_ref[_at(vw)])

        def _accum(j, masked):
            ks = pl.ds(pl.multiple_of(j * sub_k, sub_k), sub_k)
            k_blk = k_ref[_at(qw, ks)]
            s = _scores(q, k_blk, sm_scale)              # [block_q, sub_k]
            if masked:
                s = _mask_scores(s, q0, k0 + j * sub_k, causal, k_valid, 1)
            p = jnp.exp(s - _lanes(lse_scr[t], sub_k))
            dp = _scores(do, v_ref[_at(vw, ks)], 1.0)
            ds = p * (dp - _lanes(dvec_scr[t], sub_k))
            dq = jnp.dot(ds.astype(q.dtype), k_blk,
                         preferred_element_type=jnp.float32)
            dq_scr[_at(qw)] += (dq if qmask is None
                              else jnp.where(qmask, dq, 0.0))

        _for_sub_blocks(*bounds, _accum)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[...] = (sm_scale * dq_scr[...]).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, heads: int, head_dim: int, v_dim: int,
                block_q: int, block_k: int, sub_k: int, causal: bool,
                q_offset: int, k_offset: int, sm_scale: float,
                k_valid: int | None):
    """dK/dV pass: grid (B, head blocks, k-blocks, q-blocks), Q innermost.

    dv_j += p_ij^T dO_i; dk_j += sm_scale * ds_ij^T q_i. Works on TRANSPOSED
    scores s^T = k q^T ``[sub_k, block_q]``: p^T and ds^T are then the left
    operands of plain matmuls (no score tile is ever transposed) and L and D
    broadcast along sublanes from their lane-dense rows.
    """
    kj = pl.program_id(2)
    qb = pl.program_id(3)
    num_qb = pl.num_programs(3)
    q_heads, v_heads = _qv_windows(heads, head_dim, dk_scr.shape[-1], v_dim,
                                   dv_scr.shape[-1])

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q0 = q_offset + qb * block_q
    k0 = k_offset + kj * block_k
    bounds = _sub_block_range(q0, k0, block_q, block_k, sub_k, causal, k_valid)

    for t, ((qw, qmask), (vw, vmask)) in enumerate(zip(q_heads, v_heads)):
        q = _only(qmask, q_ref[_at(qw)])
        do = _only(vmask, do_ref[_at(vw)])
        lse = _finite_ref(lse_ref[t:t + 1, :])           # [1, block_q]
        dvec = dvec_ref[t:t + 1, :]

        def _accum(j, masked):
            ks = pl.ds(pl.multiple_of(j * sub_k, sub_k), sub_k)
            st = _scores(k_ref[_at(qw, ks)], q, sm_scale)     # [sub_k, block_q]
            if masked:
                st = _mask_scores(st, q0, k0 + j * sub_k, causal, k_valid, 0)
            pt = jnp.exp(st - lse)
            dv = jnp.dot(pt.astype(do.dtype), do,
                         preferred_element_type=jnp.float32)
            dpt = _scores(v_ref[_at(vw, ks)], do, 1.0)
            dst = pt * (dpt - dvec)
            dk = jnp.dot(dst.astype(q.dtype), q,
                         preferred_element_type=jnp.float32)
            dv_scr[_at(vw, ks)] += dv        # q and do carry this head's lanes
            dk_scr[_at(qw, ks)] += dk        # only, so dv and dk do too

        _for_sub_blocks(*bounds, _accum)

    @pl.when(qb == num_qb - 1)
    def _finalize():
        dk_ref[...] = (sm_scale * dk_scr[...]).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=tuple(range(6, 15)))
def _flash_dq(q, k, v, g, lse, dvec, causal, q_offset, k_offset, sm_scale,
              block_q, block_k, interpret, k_valid=None, sub_k=None):
    """q [B,Sq,H,D], g [B,Sq,H,Dv]; k [B,Sk,H,D], v [B,Sk,H,Dv]; lse, dvec
    [B,H,Sq] f32 -> dq."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq, bk, sub_k = _resolve_blocks(sq, sk, block_q, block_k, sub_k)
    per, dp, dvp, hp = _require_layout(h, d, v.shape[-1])
    qspec, ospec, qrow, kspec, vspec = _specs(per, dp, dvp, bq, bk,
                                              q_inner=False)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, heads=per, head_dim=dp, v_dim=dvp,
                          block_q=bq, block_k=bk, sub_k=sub_k, causal=causal,
                          q_offset=q_offset, k_offset=k_offset,
                          sm_scale=sm_scale, k_valid=k_valid),
        grid=(b, hp // per, sq // bq, sk // bk),
        in_specs=[qspec, kspec, vspec, ospec, qrow, qrow],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, sq, hp * dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, per * dp), jnp.float32),
                        pltpu.VMEM((per, bq, _LANES), jnp.float32),
                        pltpu.VMEM((per, bq, _LANES), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_dq",
    )(_to_blocks(q, dp, hp), _to_blocks(k, dp, hp), _to_blocks(v, dvp, hp),
      _to_blocks(g, dvp, hp), _rows(lse, per, hp), _rows(dvec, per, hp))
    return _from_blocks(dq, h, d, dp)


@functools.partial(jax.jit, static_argnums=tuple(range(6, 15)))
def _flash_dkv(q, k, v, g, lse, dvec, causal, q_offset, k_offset, sm_scale,
               block_q, block_k, interpret, k_valid=None, sub_k=None):
    """Same operands as :func:`_flash_dq` -> (dk, dv)."""
    b, sq, h, d = q.shape
    sk, dv_ = k.shape[1], v.shape[-1]
    bq, bk, sub_k = _resolve_blocks(sq, sk, block_q, block_k, sub_k)
    per, dp, dvp, hp = _require_layout(h, d, dv_)
    qspec, ospec, qrow, kspec, vspec = _specs(per, dp, dvp, bq, bk,
                                              q_inner=True)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, heads=per, head_dim=dp, v_dim=dvp,
                          block_q=bq, block_k=bk, sub_k=sub_k, causal=causal,
                          q_offset=q_offset, k_offset=k_offset,
                          sm_scale=sm_scale, k_valid=k_valid),
        grid=(b, hp // per, sk // bk, sq // bq),
        in_specs=[kspec, vspec, qspec, ospec, qrow, qrow],
        out_specs=[kspec, vspec],
        out_shape=[jax.ShapeDtypeStruct((b, sk, hp * dp), k.dtype),
                   jax.ShapeDtypeStruct((b, sk, hp * dvp), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, per * dp), jnp.float32),
                        pltpu.VMEM((bk, per * dvp), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="flash_dkv",
    )(_to_blocks(k, dp, hp), _to_blocks(v, dvp, hp), _to_blocks(q, dp, hp),
      _to_blocks(g, dvp, hp), _rows(lse, per, hp), _rows(dvec, per, hp))
    return _from_blocks(dk, h, d, dp), _from_blocks(dv, h, dv_, dvp)


@functools.lru_cache(maxsize=None)
def _partitioned_bwd(causal, q_offset, k_offset, sm_scale, block_q, block_k,
                     interpret, k_valid):
    """(q, k, v, lse, g, dvec) -> (dq, dk, dv), batch/head-partitioned.

    Pallas FA2 backward: two block kernels (dQ; dK/dV) over the saved
    logsumexp — O(S) memory, the S x S matrices never leave VMEM. ``lse`` and
    ``dvec`` arrive as [B,H,Sq] so every operand has the batch and heads dims
    the partition rule shards."""

    def impl(q, k, v, lse, g, dvec):
        args = (q, k, v, g, lse, dvec, causal, q_offset, k_offset, sm_scale,
                block_q, block_k, interpret, k_valid)
        return (_flash_dq(*args),) + _flash_dkv(*args)

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
# dQ, dK and dV — 5 matmul units where flash_dq + flash_dkv execute 7 — with
# the D = rowsum(dO . O) reduction inside it. A sequence that is no multiple
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
    TRANSPOSED scores s^T = k q^T ``[skp, sqp]`` like the dK/dV kernel: lse,
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
