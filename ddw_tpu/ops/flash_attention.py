"""Flash attention as a Pallas TPU kernel.

The reference stack has no attention anywhere (SURVEY.md §5 "Long-context ...
Absent") — this op exists because long-context support is first-class in this
framework: it is the local-block compute of :mod:`ddw_tpu.parallel.ring_attention`
(sequence parallelism) and the attention path of the ViT model family.

Design (Dao et al. flash attention, TPU-first):
- grid over (batch*heads, Q blocks); K/V streamed block-by-block inside a
  ``fori_loop`` with running max / normalizer / accumulator in VMEM scratch —
  O(S) memory instead of the O(S^2) score matrix, scores never leave VMEM;
- block sizes default to 128 (MXU/VPU native tile), f32 accumulation with inputs
  in bf16 or f32;
- causal masking by global position (supports the ring-attention case where this
  rank's K block sits at a rotated global offset);
- backward pass as two Pallas kernels (FA2 schedule): the forward saves the
  per-row logsumexp; dQ streams K/V blocks, dK/dV streams Q/dO blocks, each
  rematerializing p = exp(s - L) blockwise in VMEM — O(S) HBM for the whole
  train step, the S x S matrices never exist in HBM;
- ``interpret=None`` means the interpreter on the CPU backend (tests) and the
  Mosaic compiler on every other backend (:mod:`ddw_tpu.ops.backend`).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.custom_partitioning import custom_partitioning
from jax.experimental.pallas import tpu as pltpu

from ddw_tpu.ops.backend import interpret_by_default

_NEG_INF = -1e30


def _bh_sharding(sharding, ndim):
    """A NamedSharding keeping the suggested (batch, heads) axes and
    replicating everything after them — the partition layout the kernels
    support (seq and head_dim must be device-local)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = tuple(sharding.spec)[:2]
    spec = spec + (None,) * (ndim - len(spec))
    return NamedSharding(sharding.mesh, P(*spec))


def _def_bh_partition(fn, impl, rule, n_in, out_ndims):
    """Register batch/head-sharded SPMD partitioning on ``fn``.

    GSPMD cannot auto-partition a Mosaic custom call, so without this the
    pjit TP/DP paths (VIT_TP_RULES, LM_TP_RULES shard attention heads over
    ``model``; DP shards batch) would all-gather the operands and run the
    kernel replicated — or fail to lower. The rule declares the leading two
    dims (batch, heads) freely shardable and everything else
    need-replication; the per-shard lowering is the kernel itself on local
    shapes. Under shard_map (the ring path) the op is already per-device and
    partitioning never engages."""

    def partition(mesh, arg_shapes, result_shape):
        bh = _bh_sharding(arg_shapes[0].sharding, 2)
        args = tuple(_bh_sharding(bh, s.ndim) for s in arg_shapes)
        outs = tuple(_bh_sharding(bh, n) for n in out_ndims)
        return mesh, impl, outs, args

    def infer(mesh, arg_shapes, result_shape):
        bh = _bh_sharding(arg_shapes[0].sharding, 2)
        return tuple(_bh_sharding(bh, n) for n in out_ndims)

    # NB: shardy requires the special-factor indices sorted, i.e. listed in
    # first-appearance order of the rule string (q before d before s).
    fn.def_partition(partition=partition, infer_sharding_from_operands=infer,
                     sharding_rule=rule,
                     need_replication_factors=("q", "d", "s"))
    return fn


@functools.lru_cache(maxsize=None)
def _partitioned_fwd(causal, q_offset, k_offset, sm_scale, block_q, block_k,
                     interpret, k_valid):
    """(q, k, v) -> (out [B,H,Sq,D], lse [B,H,Sq]) with SPMD partitioning over
    batch/heads. Cached per static config (the custom_partitioning object must
    be built once per config, not per trace)."""

    def impl(q, k, v):
        out, lse = _flash_forward(q, k, v, causal, q_offset, k_offset,
                                  sm_scale, block_q, block_k, interpret,
                                  k_valid)
        b, h, sq, _ = q.shape
        return out, lse.reshape(b, h, sq)

    fn = custom_partitioning(impl)
    return _def_bh_partition(
        fn, impl, "b h q d, b h s d, b h s d -> b h q d, b h q",
        n_in=3, out_ndims=(4, 3))


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


def _masked_scores(q, k_blk, q_start, k_start, causal, sm_scale,
                   block_q, block_k, k_valid=None):
    """QK^T with the causal + key-padding masks applied at global positions —
    shared by the forward and both backward kernels so the masking can never
    desynchronize. ``k_valid`` (static) masks keys at global position >= it
    (the padded tail when the sequence was padded up to a block multiple)."""
    sc = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * sm_scale
    if causal or k_valid is not None:
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        keep = jnp.full((block_q, block_k), True)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            keep = kpos <= qpos
        if k_valid is not None:
            keep = jnp.logical_and(keep, kpos < k_valid)
        sc = jnp.where(keep, sc, _NEG_INF)
    return sc


def _guarded_exp(sc, ref, masked):
    """p = exp(s - ref) with the fully-masked-row guard: where s == _NEG_INF the
    subtraction cancels in f32 (exp -> 1), so re-zero masked entries explicitly.
    Load-bearing in all three kernels — keeps masked rows at zero output and
    zero gradient."""
    p = jnp.exp(sc - ref)
    if masked:
        p = jnp.where(sc > _NEG_INF / 2, p, 0.0)
    return p


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                  block_k: int, causal: bool, q_offset: int, k_offset: int,
                  sm_scale: float, block_q: int, k_valid: int | None):
    """One (batch*head, q-block, k-block) grid step of online-softmax attention.

    The K loop is a GRID dimension (innermost), so Mosaic double-buffers the
    K/V block DMAs across steps; the running (max, normalizer, accumulator)
    lives in VMEM scratch that persists along the k dimension, initialized at
    kb==0 and written to the output block at the last kb. QK^T and PV run in
    the input dtype (bf16 -> full MXU rate) with f32 accumulation
    (preferred_element_type); softmax bookkeeping is f32 on the VPU. Fully
    -future K blocks under causal masking are skipped via pl.when."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_last = q_offset + qi * block_q + block_q - 1
    k_first = k_offset + kb * block_k
    visible = (k_first <= q_last) if causal else True
    if k_valid is not None:
        visible = visible & (k_first < k_valid)

    @pl.when(visible)
    def _attend():
        q = q_ref[0]                                     # [block_q, d]
        k_blk = k_ref[0]                                 # [block_k, d]
        v_blk = v_ref[0]
        s = _masked_scores(q, k_blk, q_offset + qi * block_q,
                           k_offset + kb * block_k, causal, sm_scale,
                           block_q, block_k, k_valid)
        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard keeps l at 0 on fully-masked rows so _finalize emits zeros
        p = _guarded_exp(s, m_new, causal or k_valid is not None)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(q.dtype), v_blk, preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(kb == num_kb - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)
        # logsumexp residual for the Pallas backward (FA2): L = m + log(l).
        # Fully-masked rows keep L ~ _NEG_INF so backward p = exp(s - L) is
        # re-zeroed there by the same s > _NEG_INF/2 guard.
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _flash_forward(q, k, v, causal, q_offset, k_offset, sm_scale, block_q,
                   block_k, interpret, k_valid=None):
    """Returns (out, lse) with lse [B*H, Sq, 1] f32 (the backward residual)."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq lengths ({sq},{sk}) must divide blocks ({block_q},{block_k})")
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, q_offset=q_offset,
        k_offset=k_offset, sm_scale=sm_scale, block_q=block_q, k_valid=k_valid)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, sk // block_k),  # k innermost: scratch carries
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        # bh and q-block steps are independent (scratch re-inits at kb==0);
        # only the innermost k dim carries state. Declaring that lets Mosaic
        # overlap DMA and compute across grid steps instead of serializing
        # the whole grid.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, causal: bool = False, q_offset: int = 0,
                    k_offset: int = 0, sm_scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None,
                    k_valid: int | None = None):
    """Flash attention: softmax(q k^T / sqrt(d)) v without materializing scores.

    q [B,H,Sq,D], k/v [B,H,Sk,D] -> [B,H,Sq,D]. ``q_offset``/``k_offset`` are the
    global positions of the local blocks (used by ring attention for causal
    masking across rotated K/V shards). ``k_valid`` (static) masks keys at
    global position >= it — the padded tail when Sk was padded to a block
    multiple (see :func:`flash_mha`).
    """
    sm_scale, interpret = _resolve_defaults(sm_scale, interpret, q.shape[-1])
    return _partitioned_fwd(causal, q_offset, k_offset, sm_scale, block_q,
                            block_k, interpret, k_valid)(q, k, v)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention_lse(q, k, v, causal: bool = False, q_offset: int = 0,
                        k_offset: int = 0, sm_scale: float | None = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool | None = None,
                        k_valid: int | None = None):
    """Flash attention that also returns the per-row logsumexp.

    Returns ``(out [B,H,Sq,D], lse [B,H,Sq] f32)`` with
    ``lse = logsumexp_k(q.k * sm_scale)`` over this call's (masked) keys. The
    residual a caller needs to softmax-combine partial attention over disjoint
    key sets — :func:`ddw_tpu.parallel.ring_attention.ring_attention` folds one
    of these per ring hop. Differentiable in both outputs (the lse cotangent
    folds into the score gradient as ``ds += p * g_lse``)."""
    sm_scale, interpret = _resolve_defaults(sm_scale, interpret, q.shape[-1])
    return _partitioned_fwd(causal, q_offset, k_offset, sm_scale, block_q,
                            block_k, interpret, k_valid)(q, k, v)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, dq_ref, dq_scr,
               *, block_q: int, block_k: int, causal: bool, q_offset: int,
               k_offset: int, sm_scale: float, k_valid: int | None):
    """dQ pass (FA2 backward): grid (BH, q-blocks, k-blocks), K innermost.

    p_ij = exp(s_ij - L_i) rematerialized per block from the saved logsumexp;
    ds_ij = p_ij * (dO_i . v_j - D_i); dq_i += sm_scale * ds_ij k_j. The S x S
    matrices exist only blockwise in VMEM.
    """
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_last = q_offset + qi * block_q + block_q - 1
    k_first = k_offset + kb * block_k
    visible = (k_first <= q_last) if causal else True
    if k_valid is not None:
        visible = visible & (k_first < k_valid)

    @pl.when(visible)
    def _accum():
        q = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        s = _masked_scores(q, k_blk, q_offset + qi * block_q,
                           k_offset + kb * block_k, causal, sm_scale,
                           block_q, block_k, k_valid)
        p = _guarded_exp(s, lse_ref[0], causal or k_valid is not None)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dvec_ref[0])
        dq_scr[:] += sm_scale * jnp.dot(
            ds.astype(q.dtype), k_blk, preferred_element_type=jnp.float32)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dvec_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, block_q: int, block_k: int, causal: bool,
                q_offset: int, k_offset: int, sm_scale: float,
                k_valid: int | None):
    """dK/dV pass: grid (BH, k-blocks, q-blocks), Q innermost.

    dv_j += p_ij^T dO_i; dk_j += sm_scale * ds_ij^T q_i.
    """
    kj = pl.program_id(1)
    qb = pl.program_id(2)
    num_qb = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_last = q_offset + qb * block_q + block_q - 1
    k_first = k_offset + kj * block_k
    visible = (k_first <= q_last) if causal else True
    if k_valid is not None:
        visible = visible & (k_first < k_valid)

    @pl.when(visible)
    def _accum():
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        s = _masked_scores(q, k_blk, q_offset + qb * block_q,
                           k_offset + kj * block_k, causal, sm_scale,
                           block_q, block_k, k_valid)
        p = _guarded_exp(s, lse_ref[0], causal or k_valid is not None)
        dv_scr[:] += jnp.dot(p.astype(do.dtype).T, do,
                             preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dvec_ref[0])
        dk_scr[:] += sm_scale * jnp.dot(
            ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32)

    @pl.when(qb == num_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _partitioned_bwd(causal, q_offset, k_offset, sm_scale, block_q, block_k,
                     interpret, k_valid):
    """(q, k, v, lse3, g, dvec3) -> (dq, dk, dv), batch/head-partitioned.

    Pallas FA2 backward: two block kernels (dQ; dK/dV) over the saved
    logsumexp — O(S) memory, the S x S matrices never leave VMEM. ``lse3`` and
    ``dvec3`` arrive as [B,H,Sq] so every operand has the (b, h) leading dims
    the partition rule shards."""

    def impl(q, k, v, lse3, g, dvec3):
        b, h, sq, d = q.shape
        sk = k.shape[2]
        bq = min(block_q, sq)
        bk = min(block_k, sk)

        qr = q.reshape(b * h, sq, d)
        kr = k.reshape(b * h, sk, d)
        vr = v.reshape(b * h, sk, d)
        gr = g.reshape(b * h, sq, d)
        lse = lse3.reshape(b * h, sq, 1)
        dvec = dvec3.reshape(b * h, sq, 1)

        qspec = pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0),
                             memory_space=pltpu.VMEM)
        qrow = pl.BlockSpec((1, bq, 1), lambda i, j, kb: (i, j, 0),
                            memory_space=pltpu.VMEM)
        kspec_stream = pl.BlockSpec((1, bk, d), lambda i, j, kb: (i, kb, 0),
                                    memory_space=pltpu.VMEM)
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, block_q=bq, block_k=bk, causal=causal,
                              q_offset=q_offset, k_offset=k_offset,
                              sm_scale=sm_scale, k_valid=k_valid),
            grid=(b * h, sq // bq, sk // bk),
            in_specs=[qspec, kspec_stream, kspec_stream, qspec, qrow, qrow],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(qr, kr, vr, gr, lse, dvec)

        kspec = pl.BlockSpec((1, bk, d), lambda i, j, qb: (i, j, 0),
                             memory_space=pltpu.VMEM)
        qspec_stream = pl.BlockSpec((1, bq, d), lambda i, j, qb: (i, qb, 0),
                                    memory_space=pltpu.VMEM)
        qrow_stream = pl.BlockSpec((1, bq, 1), lambda i, j, qb: (i, qb, 0),
                                   memory_space=pltpu.VMEM)
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, block_q=bq, block_k=bk, causal=causal,
                              q_offset=q_offset, k_offset=k_offset,
                              sm_scale=sm_scale, k_valid=k_valid),
            grid=(b * h, sk // bk, sq // bq),
            in_specs=[kspec, kspec, qspec_stream, qspec_stream, qrow_stream,
                      qrow_stream],
            out_specs=[kspec, kspec],
            out_shape=[jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                       jax.ShapeDtypeStruct((b * h, sk, d), v.dtype)],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(kr, vr, qr, gr, lse, dvec)

        return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
                dv.reshape(b, h, sk, d))

    fn = custom_partitioning(impl)
    return _def_bh_partition(
        fn, impl,
        "b h q d, b h s d, b h s d, b h q, b h q d, b h q -> "
        "b h q d, b h s d, b h s d",
        n_in=6, out_ndims=(4, 4, 4))


def _bwd_impl(causal, q_offset, k_offset, sm_scale, block_q, block_k, interpret,
              k_valid, residuals, g, g_lse=None):
    """Shared VJP body. ``g_lse`` (the lse-output cotangent, [B,H,Sq] or None)
    folds into the score gradient: d lse_i / d s_ij = p_ij, so
    ds = p * (dp - D + g_lse) — carried by passing D' = D - g_lse through the
    unchanged kernels."""
    q, k, v, out, lse3 = residuals
    sm_scale, interpret = _resolve_defaults(sm_scale, interpret, q.shape[-1])
    # D_i = dO_i . O_i (the softmax-normalizer correction), cheap elementwise
    # — stays outside the partitioned call, GSPMD shards it fine.
    dvec3 = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        dvec3 = dvec3 - g_lse.astype(jnp.float32)
    return _partitioned_bwd(causal, q_offset, k_offset, sm_scale, block_q,
                            block_k, interpret, k_valid)(q, k, v, lse3, g, dvec3)


def _fwd(q, k, v, causal, q_offset, k_offset, sm_scale, block_q, block_k,
         interpret, k_valid):
    sm_scale, interpret = _resolve_defaults(sm_scale, interpret, q.shape[-1])
    out, lse3 = _partitioned_fwd(causal, q_offset, k_offset, sm_scale, block_q,
                                 block_k, interpret, k_valid)(q, k, v)
    return out, (q, k, v, out, lse3)


def _bwd(causal, q_offset, k_offset, sm_scale, block_q, block_k, interpret,
         k_valid, residuals, g):
    return _bwd_impl(causal, q_offset, k_offset, sm_scale, block_q, block_k,
                     interpret, k_valid, residuals, g)


flash_attention.defvjp(_fwd, _bwd)


def _fwd_lse(q, k, v, causal, q_offset, k_offset, sm_scale, block_q, block_k,
             interpret, k_valid):
    sm_scale, interpret = _resolve_defaults(sm_scale, interpret, q.shape[-1])
    out, lse3 = _partitioned_fwd(causal, q_offset, k_offset, sm_scale, block_q,
                                 block_k, interpret, k_valid)(q, k, v)
    return (out, lse3), (q, k, v, out, lse3)


def _bwd_lse(causal, q_offset, k_offset, sm_scale, block_q, block_k, interpret,
             k_valid, residuals, gs):
    g, g_lse = gs
    return _bwd_impl(causal, q_offset, k_offset, sm_scale, block_q, block_k,
                     interpret, k_valid, residuals, g, g_lse)


flash_attention_lse.defvjp(_fwd_lse, _bwd_lse)


def _pick_block(s: int, block: int, dtype) -> int:
    """Choose a Mosaic-tile-aligned block size for a sequence of length ``s``.

    The block is the second-minor dim of the kernel's VMEM tiles, so it must be
    a multiple of the sublane tile (16 for bf16/f16, 8 otherwise); ``s`` is
    then padded UP to a multiple of the block rather than the block shrunk to
    ``s`` (a block of exactly s=100 lowers in interpret mode but fails Mosaic
    tiling on real TPU)."""
    tile = 16 if dtype in (jnp.bfloat16, jnp.float16) else 8
    aligned = -(-max(s, 1) // tile) * tile
    return max(tile, min(block, aligned) // tile * tile)


def _pad_seq(x, mult):
    """Zero-pad the sequence axis (dim 2 of [B,H,S,D]) up to a multiple."""
    s = x.shape[2]
    pad = (-s) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


# ---------------------------------------------------------------------------
# Size-based dispatch: the Pallas kernels exist for long-context O(S) memory,
# but at moderate S a plain XLA attention is FASTER on TPU (measured on v5e,
# differential timing: ViT shapes [256,4,197,48] fwd+grad 3.2 ms XLA vs
# 10.1 ms Pallas; LM shapes [8,8,2048,64] causal 14.5 ms vs 36.2 ms — the
# FA2 backward's blockwise rematerialization can't beat one fused S² einsum
# while the score matrix still fits). The model-facing entries therefore
# dispatch on the score-matrix footprint: plain XLA when small, jax.checkpoint
# XLA (O(S) residuals, S² transient in backward) when moderate, Pallas flash
# when the S² matrix is genuinely memory-infeasible.
# ---------------------------------------------------------------------------

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


def _attn_impl(q, k, impl: str) -> str:
    if impl != "auto":
        return impl
    b, h, sq, _ = q.shape
    score_bytes = b * h * sq * k.shape[2] * 4
    if score_bytes <= _XLA_PLAIN_MAX:
        return "xla"
    if score_bytes <= _XLA_CKPT_MAX:
        return "xla_ckpt"
    return "pallas"


def flash_mha(q, k, v, causal: bool = False, sm_scale: float | None = None,
              block_q: int = 128, block_k: int = 128,
              interpret: bool | None = None, impl: str = "auto") -> jnp.ndarray:
    """Attention for arbitrary sequence lengths (the model-facing entry).

    ``impl``: ``auto`` (size-based dispatch, see module comment), ``xla``,
    ``xla_ckpt`` (rematerialized backward), or ``pallas`` (the flash kernel —
    pads Sq/Sk to tile-aligned block multiples, masks padded keys via
    ``k_valid``, slices padded query rows back off, so ViT's 196-patch
    sequences or any other length run on the same kernel the LM uses)."""
    return flash_mha_lse(q, k, v, causal, sm_scale, block_q, block_k,
                         interpret, impl)[0]


def flash_mha_lse(q, k, v, causal: bool = False, sm_scale: float | None = None,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool | None = None, impl: str = "auto"):
    """Padded-length attention with logsumexp — ``(out, lse [B,H,Sq])``.

    Same dispatch and padding contract as :func:`flash_mha`; the lse rows for
    padded queries are sliced off with the outputs. Ring attention calls this
    per hop so arbitrary local shard lengths work."""
    with jax.named_scope("attention"):      # every tier, for a profile's split
        return _dispatch_lse(q, k, v, causal, sm_scale, block_q, block_k,
                             interpret, impl)


def _dispatch_lse(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                  impl):
    chosen = _attn_impl(q, k, impl)
    if chosen in ("xla", "xla_ckpt"):
        scale, _ = _resolve_defaults(sm_scale, interpret, q.shape[-1])
        fn = functools.partial(_xla_attention_lse, causal=causal, q_offset=0,
                               k_offset=0, sm_scale=scale, k_valid=None)
        if chosen == "xla_ckpt":
            fn = jax.checkpoint(fn)
        return fn(q, k, v)
    sq, sk = q.shape[2], k.shape[2]
    bq = _pick_block(sq, block_q, q.dtype)
    bk = _pick_block(sk, block_k, k.dtype)
    qp = _pad_seq(q, bq)
    kp = _pad_seq(k, bk)
    vp = _pad_seq(v, bk)
    k_valid = sk if kp.shape[2] != sk else None
    out, lse = flash_attention_lse(qp, kp, vp, causal, 0, 0, sm_scale, bq, bk,
                                   interpret, k_valid)
    if qp.shape[2] != sq:
        out, lse = out[:, :, :sq], lse[:, :, :sq]
    return out, lse
