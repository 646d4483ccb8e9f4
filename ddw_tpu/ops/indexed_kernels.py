"""Chosen-key attention as Pallas TPU kernels: the score tiles stay in VMEM.

The kernels of :mod:`ddw_tpu.ops.indexed_attention`'s ``pallas`` tier, in the
manner of :mod:`ddw_tpu.ops.flash_attention`'s streaming tier (online softmax,
a saved log-sum-exp, ONE backward kernel that makes a score tile, its
exponential and dS once and takes dQ, dK and dV from them, the interpreter on
the CPU backend, wrappers under ``jit``) with what that tier lacks:

- **the choice is a mask tile.** ``mask [B, S, S]``, one byte a (query, key)
  pair and the same for every head of a query, is read a ``[block_q,
  block_k]`` tile at a time and added to the float32 scores in VMEM as a bias
  of 0 or -1e30. Causality is part of the choice, so no position is compared.
  A pair of blocks above the diagonal is never visited (nothing is fetched
  for it either); one below it in which no query chose a key is an exact
  no-op;
- **grouped-query heads.** Operands come as the projections make them, ``q
  [B, S, H, D]`` and ``k, v [B, S, KV, D]`` viewed ``[B, S, H*D]`` and ``[B,
  S, KV*D]``: a grid step serves the ``H // KV`` query heads of one key head,
  a 128-lane slice of the q tile each, so K and V are read once a group and
  nothing is repeated or transposed in HBM. ``D`` under 128 is zero-padded to
  it, which changes no score;
- **the indexer's target** (``indexed_target``): ``sum_h p_h[t, s] / H``, a
  number a pair and not a head, formed from the saved log-sum-exp in a pass
  of a forward's shape (the score tile again, ``exp(s - lse)``, the sum over
  the heads of a group in the kernel and over the groups in the output block,
  the groups being the innermost grid dimension) and written once, float32.
  Blocks above the diagonal are not written: the caller reads the target
  under the mask.

Arithmetic: operands in their own dtype into float32 scores, float32
statistics, probabilities rounded to the value dtype for the second product.
A query always chose a key (itself, if no other), so no row's log-sum-exp is
the mask's -1e30; inside a block a row may well have chosen none, which the
running maximum's guard (``_finite_ref``) makes an exact no-op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddw_tpu.ops.backend import interpret_by_default
from ddw_tpu.ops.flash_attention import (_LANES, _NEG_INF, _dq_through_hbm,
                                         _finite_ref, _lanes, _scores)

# Blocks: the largest that divide S, up to 256 queries by 512 keys. With
# eight query heads a grid step, that keeps the kernels' working set (the
# backward's q and dO tiles of eight heads, double-buffered, their float32
# accumulator and a few score tiles) under the 16 MiB of VMEM a kernel may
# use without asking. 512 x 512 needs 20 MiB and was 5 % faster alone (v5e,
# the cell's shape, ms for forward / dQ / dK,dV / target, the backward then
# two kernels: 8.71 / 11.78 / 14.03 / 5.54 against 9.28 / 12.34 / 14.83 /
# 5.91; tools/indexed_sweep.py, PR 33), but a train step of two layers or
# more whose kernels raised their limit to 32 MiB never came back from the
# chip (PERF.md section 6, PR 33): these kernels ask for nothing.
BLOCKS_Q = (256, 128)
BLOCKS_K = (512, 256, 128)
# The one-pass backward kernel may take key blocks of 1024 besides: its MXU
# runs are twice as long (the scores' and dP's products stream 1,024 rows
# past a weight tile) at 11.4 MiB of VMEM, and the pairs above the diagonal
# that the wider block visits for nothing are 6 % of the visit at S = 8,192.
# ms for forward / backward / target at the cell's shape (v5e, tools/
# indexed_sweep.py, PR 46; the dQ and dK/dV kernels this backward replaced
# took 12.32 + 14.81 at 256 x 512): 256 x 512 9.26 / 21.67 / 5.92; 256 x 1024
# 9.16 / 19.76 / 5.06 (the forward and the target keep 256 x 512: not this
# PR's); 128 x 1024 10.93 / 21.13 / 5.52; 128 x 512 13.30 / 24.81 / 6.79;
# 512 x 256 9.15 / 25.07 / 7.55; 256 x 256 10.83 / 28.23 / 8.29; 512 x 512
# does not fit (the backward plans 16.006 MiB). Sequences no workload runs
# lose 2 % of this kernel to the diagonal at the wider block (S = 2,048:
# 1.678 for 1.642; 4,096: not measured).
BLOCKS_K_BWD = (1024,) + BLOCKS_K
MIN_SEQ = 512               # below it "auto" keeps the XLA tiles
HEAD_DIMS = (64, 128)


def pick_blocks(s: int):
    """``(block_q, block_k, the backward kernel's block_k)`` for a sequence
    of ``s``: the largest of ``BLOCKS_Q``, of ``BLOCKS_K`` and of
    ``BLOCKS_K_BWD`` that divide it (None: the kernels do not take ``s``)."""
    if s % _LANES:
        return None
    return tuple(next(b for b in blocks if s % b == 0)
                 for blocks in (BLOCKS_Q, BLOCKS_K, BLOCKS_K_BWD))


def _visited(qb, kb, block_q: int, block_k: int):
    """Whether the pair of blocks lies on or below the diagonal."""
    return kb * block_k < (qb + 1) * block_q


def _bias(mask_ref):
    """The mask tile as a float32 bias: 0 on a chosen pair, -1e30 (which
    absorbs any score) elsewhere. Arithmetic, not a compare: the byte tile's
    layout is not the float tile's."""
    chosen = mask_ref[...].astype(jnp.int32).astype(jnp.float32)
    return (chosen - 1.0) * -_NEG_INF


def _column(row, block_q: int):
    """A lane-dense ``[1, block_q]`` statistic as a lane-replicated
    ``[block_q, 128]`` column."""
    return jnp.broadcast_to(row, (_LANES, block_q)).T


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, group: int, head_dim: int, block_q: int,
                block_k: int, sm_scale: float):
    """One (batch, key head, q block, k block) grid step, k innermost: the
    online softmax of the ``group`` query heads of the key head in turn, all
    under one mask tile."""
    qb, kb, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_visited(qb, kb, block_q, block_k))
    def _attend():
        bias = _bias(mask_ref)
        k, v = k_ref[...], v_ref[...]
        for t in range(group):
            lanes = slice(t * head_dim, (t + 1) * head_dim)
            s = _scores(q_ref[:, lanes], k, sm_scale) + bias
            m_prev = m_scr[t]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a row that chose no key yet keeps l at 0 (see _finite_ref)
            p = jnp.exp(s - _lanes(_finite_ref(m_new), block_k))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[t] = alpha * l_scr[t] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[:, lanes] = (
                acc_scr[:, lanes] * _lanes(alpha, head_dim)
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32))
            m_scr[t] = m_new

    @pl.when(kb == nk - 1)
    def _finalize():
        for t in range(group):
            lanes = slice(t * head_dim, (t + 1) * head_dim)
            l = jnp.maximum(l_scr[t], 1e-30)
            lse_ref[t:t + 1, :] = (m_scr[t] + jnp.log(l)).T[:1]
            o_ref[:, lanes] = (acc_scr[:, lanes] / _lanes(l, head_dim)
                               ).astype(o_ref.dtype)


def _bwd_kernel(k_ref, v_ref, q_ref, do_ref, mask_ref, lse_ref, dvec_ref,
                dk_ref, dv_ref, dq_hbm, spill, dk_scr, dv_scr, dq_scr, dq_in,
                dq_out, sems, state, *, group: int, head_dim: int,
                block_q: int, block_k: int, sm_scale: float):
    """The whole backward pass of one (batch, key head, k block, q block) grid
    step, q innermost, on TRANSPOSED scores ``k q^T [block_k, block_q]`` under
    the mask tile's bias, transposed in VMEM (no transposed mask in HBM): for
    each of the group's heads ``p^T = exp(s^T - L)`` and ``ds^T = p^T (v dO^T
    - D)`` are made ONCE and all three gradients come of them. p^T and ds^T
    are the left operands of plain matmuls for dV and dK, dQ contracts ds^T
    over its first dimension, the log-sum-exp and D broadcast along sublanes
    from their lane-dense rows, and the group's heads add up in one dK and
    one dV accumulator.

    dK and dV sum over the inner grid dimension in VMEM scratch. dQ sums over
    the OUTER one: a q block is visited once a key block on or below its
    diagonal, and between visits the group's float32 sum waits in ``spill``,
    a ``[S, group * head_dim]`` buffer in HBM read and written by the
    kernel's own DMAs (``flash_attention._dq_through_hbm``, the streaming
    kernels' ``"hbm"`` home), and is rounded into the output on the last key
    block the q block sees."""
    kb, qb, nq = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def accumulate(dq_at):
        """Add this step's dK, dV to their scratch and its dQ to
        ``dq_scr[dq_at(a head's lanes)]``."""
        bias = _bias(mask_ref).T        # once a step, for all the heads
        k, v = k_ref[...], v_ref[...]
        for t in range(group):
            lanes = slice(t * head_dim, (t + 1) * head_dim)
            q, do = q_ref[:, lanes], do_ref[:, lanes]
            st = _scores(k, q, sm_scale) + bias
            pt = jnp.exp(st - lse_ref[t:t + 1, :])
            dv_scr[...] += jnp.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dst = (pt * (_scores(v, do, 1.0) - dvec_ref[t:t + 1, :])
                   ).astype(q.dtype)
            dk_scr[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
            dq_scr[dq_at(lanes)] += jax.lax.dot_general(
                dst, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _dq_through_hbm(dq_hbm, spill, dq_scr, dq_in, dq_out, sems, state,
                    accumulate, _visited(qb, kb, block_q, block_k),
                    _last_k(qb, block_q, block_k), block_q, sm_scale)

    @pl.when(qb == nq - 1)
    def _finalize():
        dk_ref[...] = (sm_scale * dk_scr[...]).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _target_kernel(q_ref, k_ref, mask_ref, lse_ref, t_ref, *, group: int,
                   head_dim: int, heads: int, block_q: int, block_k: int,
                   sm_scale: float):
    """The indexer's target: grid (batch, q block, k block, key head), the key
    heads innermost, their groups' probabilities adding up in the output
    block."""
    qb, kb, g = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(_visited(qb, kb, block_q, block_k))
    def _sum():
        bias = _bias(mask_ref)
        k = k_ref[...]
        total = None
        for t in range(group):
            lanes = slice(t * head_dim, (t + 1) * head_dim)
            s = _scores(q_ref[:, lanes], k, sm_scale) + bias
            p = jnp.exp(s - _lanes(_column(lse_ref[t:t + 1, :], block_q),
                                   block_k))
            total = p if total is None else total + p
        total = total * (1.0 / heads)

        @pl.when(g == 0)
        def _first():
            t_ref[...] = total

        @pl.when(g > 0)
        def _more():
            t_ref[...] += total


def _last_k(qb, block_q: int, block_k: int):
    """The last key block a q block visits."""
    return ((qb + 1) * block_q - 1) // block_k


def _specs(group: int, dp: int, bq: int, bk: int, q_inner: bool):
    """Block specs for a grid (batch, key head, outer, inner) with the k
    blocks inner (or the q blocks): the q-side tile of a group, its float32
    rows, the k-side tile and the mask tile. A step the diagonal cuts off
    maps to the block of the nearest step that is visited, so nothing is
    fetched for it."""
    def qk(g):
        if q_inner:     # (b, h, kb, qb): the first q block that sees kb
            return jnp.maximum(g[3], g[2] * bk // bq), g[2]
        return g[2], jnp.minimum(g[3], _last_k(g[2], bq, bk))

    vmem = dict(memory_space=pltpu.VMEM)
    qspec = pl.BlockSpec((None, bq, group * dp),
                         lambda *g: (g[0], qk(g)[0], g[1]), **vmem)
    qrow = pl.BlockSpec((None, None, group, bq),
                        lambda *g: (g[0], g[1], 0, qk(g)[0]), **vmem)
    kspec = pl.BlockSpec((None, bk, dp),
                         lambda *g: (g[0], qk(g)[1], g[1]), **vmem)
    mask = pl.BlockSpec((None, bq, bk),
                        lambda *g: (g[0], *qk(g)), **vmem)
    return qspec, qrow, kspec, mask


def _views(q, k, *more):
    """``q [B,S,H,D]`` and ``k [B,S,KV,D]``-shaped operands as ``[B,S,H*dp]``
    and ``[B,S,KV*dp]`` (D zero-padded up to a multiple of 128 lanes), and
    ``(group, dp)``."""
    b, s, h, d = q.shape
    dp = -(-d // _LANES) * _LANES

    def view(x):
        if dp != d:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        return x.reshape(b, s, -1)

    return [view(x) for x in (q, k, *more)], h // k.shape[2], dp


def _unview(x, d: int, dp: int):
    b, s, width = x.shape
    return x.reshape(b, s, width // dp, dp)[..., :d]


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret, operands, carried: int = 1):
    # the ``carried`` innermost grid dimensions go in order (scratch, or an
    # output block, lives across their steps); the others are independent
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (4 - carried)
            + ("arbitrary",) * carried),
        interpret=interpret, name=name)(*operands)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _forward(q, k, v, mask, sm_scale, bq, bk, interpret):
    """-> ``(out [B,S,H,D], lse [B,KV,H//KV,S] f32)``."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    (qv, kview, vv), group, dp = _views(q, k, v)
    qspec, qrow, kspec, mspec = _specs(group, dp, bq, bk, q_inner=False)
    out, lse = _call(
        functools.partial(_fwd_kernel, group=group, head_dim=dp, block_q=bq,
                          block_k=bk, sm_scale=sm_scale),
        "indexed_fwd", (b, kv, s // bq, s // bk),
        [qspec, kspec, kspec, mspec], [qspec, qrow],
        [jax.ShapeDtypeStruct(qv.shape, q.dtype),
         jax.ShapeDtypeStruct((b, kv, group, s), jnp.float32)],
        [pltpu.VMEM((group, bq, _LANES), jnp.float32),
         pltpu.VMEM((group, bq, _LANES), jnp.float32),
         pltpu.VMEM((bq, group * dp), jnp.float32)],
        interpret, (qv, kview, vv, mask))
    return _unview(out, d, dp), lse


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _backward(q, k, v, mask, g, lse, dvec, sm_scale, bq, bk, interpret):
    """The operands, the output's cotangent ``g``, the saved ``lse`` and
    ``dvec = rowsum(g * out)`` (both ``[B,KV,H//KV,S]``) -> (dq, dk, dv), one
    ``pallas_call``. It keeps the name the dK/dV kernel had, ``indexed_dkv``:
    the benchmark finds the kernels by name. Nothing is transposed in HBM."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    (qv, kview, vv, gv), group, dp = _views(q, k, v, g)
    lanes = group * dp
    qspec, qrow, kspec, mspec = _specs(group, dp, bq, bk, q_inner=True)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    dk, dv, dq, _ = _call(
        functools.partial(_bwd_kernel, group=group, head_dim=dp, block_q=bq,
                          block_k=bk, sm_scale=sm_scale),
        "indexed_dkv", (b, kv, s // bk, s // bq),
        [kspec, kspec, qspec, qspec, mspec, qrow, qrow],
        [kspec, kspec, in_hbm, in_hbm],
        [jax.ShapeDtypeStruct(kview.shape, k.dtype),
         jax.ShapeDtypeStruct(vv.shape, v.dtype),
         jax.ShapeDtypeStruct(qv.shape, q.dtype),
         jax.ShapeDtypeStruct((s, lanes), jnp.float32)],    # dQ's sum
        [pltpu.VMEM((bk, dp), jnp.float32),
         pltpu.VMEM((bk, dp), jnp.float32),
         pltpu.VMEM((2, bq, lanes), jnp.float32),
         pltpu.VMEM((bq, lanes), jnp.float32),
         pltpu.VMEM((2, bq, lanes), q.dtype),
         pltpu.SemaphoreType.DMA((2,)),
         pltpu.SMEM((3,), jnp.int32)],
        interpret, (kview, vv, qv, gv, mask, lse, dvec),
        carried=4)      # the DMAs' ``state`` runs through the whole grid
    return _unview(dq, d, dp), _unview(dk, d, dp), _unview(dv, d, dp)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _target(q, k, mask, lse, sm_scale, bq, bk, interpret):
    """-> ``sum_h p_h / H [B,S,S]`` float32, defined on and below the
    diagonal's blocks (read it under the mask)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    (qv, kview), group, dp = _views(q, k)
    last = lambda qb, kb: jnp.minimum(kb, _last_k(qb, bq, bk))   # noqa: E731
    vmem = dict(memory_space=pltpu.VMEM)
    tile = pl.BlockSpec((None, bq, bk),
                        lambda b_, qb, kb, g: (b_, qb, last(qb, kb)), **vmem)
    return _call(
        functools.partial(_target_kernel, group=group, head_dim=dp, heads=h,
                          block_q=bq, block_k=bk, sm_scale=sm_scale),
        "indexed_target", (b, s // bq, s // bk, kv),
        [pl.BlockSpec((None, bq, group * dp),
                      lambda b_, qb, kb, g: (b_, qb, g), **vmem),
         pl.BlockSpec((None, bk, dp),
                      lambda b_, qb, kb, g: (b_, last(qb, kb), g), **vmem),
         tile,
         pl.BlockSpec((None, None, group, bq),
                      lambda b_, qb, kb, g: (b_, g, 0, qb), **vmem)],
        tile, jax.ShapeDtypeStruct((b, s, s), jnp.float32), [],
        interpret, (qv, kview, mask, lse),
        carried=2)      # a row's steps past the diagonal share its last block


def _scale(q) -> float:
    return float(q.shape[-1]) ** -0.5


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attend(q, k, v, mask, bq, bk, bk_bwd, interpret):
    return _attend_fwd(q, k, v, mask, bq, bk, bk_bwd, interpret)[0]


def _attend_fwd(q, k, v, mask, bq, bk, bk_bwd, interpret):
    out, lse = _forward(q, k, v, mask, _scale(q), bq, bk, interpret)
    # a block rematerialised whole keeps both (models/lm.py saves the name),
    # so its backward pass runs no forward kernel a second time
    out = checkpoint_name(out, "attention_out")
    lse = checkpoint_name(lse, "attention_out")
    return (out, lse), (q, k, v, mask, out, lse)


def _attend_bwd(bq, bk, bk_bwd, interpret, residuals, cotangents):
    q, k, v, mask, out, lse = residuals
    g, _ = cotangents       # the log-sum-exp is a constant to its one reader
    # Under ``remat`` the mask is made again for this pass, and nothing but
    # the barrier ties it to the cotangent: without it the compiler's schedule
    # makes a layer's mask (S x S bytes a row) before the layer's expert
    # blocks have gone backward, and holds it through their peak (128 MiB at
    # the cell's shape, PERF.md section 6, PR 46).
    mask, g = jax.lax.optimization_barrier((mask, g))
    dvec = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dvec = dvec.transpose(0, 2, 1).reshape(lse.shape)
    return *_backward(q, k, v, mask, g, lse, dvec, _scale(q), bq, bk_bwd,
                      interpret), None


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend_chosen(q, k, v, mask, *, block_q: int | None = None,
                  block_k: int | None = None, interpret: bool | None = None):
    """``q [B,S,H,D]``, ``k, v [B,S,KV,D]``, ``mask [B,S,S]`` int8 (1: the
    query attends to the key, 0: not; causal, and no row empty) -> the
    attention output ``[B,S,H,D]`` (differentiable in q, k, v) and the
    indexer's target ``sum_h p_h / H [B,S,S]`` float32, a constant to the
    gradient and defined only where the mask is set. Blocks default to
    ``pick_blocks(S)``; a ``block_k`` given is the backward kernel's too."""
    s = q.shape[1]
    bq, bk, bk_bwd = (block_q, block_k, block_k) if block_q and block_k else (
        pick_blocks(s) or (_LANES,) * 3)
    if s % bq or s % bk:
        raise ValueError(f"sequence {s} is no multiple of the kernels' "
                         f"blocks ({bq}, {bk})")
    if interpret is None:
        interpret = interpret_by_default()
    stop = jax.lax.stop_gradient
    mask = stop(mask)
    out, lse = _attend(q, k, v, mask, bq, bk, bk_bwd, interpret)
    target = _target(stop(q), stop(k), mask, stop(lse), _scale(q), bq, bk,
                     interpret)
    return out, target
