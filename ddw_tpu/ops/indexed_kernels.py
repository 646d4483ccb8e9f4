"""Chosen-key attention as Pallas TPU kernels: the score tiles stay in VMEM.

The kernels of :mod:`ddw_tpu.ops.indexed_attention`'s ``pallas`` tier, in the
manner of :mod:`ddw_tpu.ops.flash_attention`'s streaming tier (online softmax,
a saved log-sum-exp, separate dQ and dK/dV passes, the interpreter on the CPU
backend, wrappers under ``jit``) with what that tier lacks:

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
from ddw_tpu.ops.flash_attention import (_LANES, _NEG_INF, _finite_ref,
                                         _lanes, _scores)

# Blocks: the largest that divide S, up to 256 queries by 512 keys. With
# eight query heads a grid step, that keeps the kernels' working set (the
# backward's q and dO tiles of eight heads, double-buffered, their float32
# accumulator and a few score tiles) under the 16 MiB of VMEM a kernel may
# use without asking. 512 x 512 needs 20 MiB and is 5 % faster alone (v5e,
# the cell's shape, ms for forward / dQ / dK,dV / target: 8.71 / 11.78 /
# 14.03 / 5.54 against 9.28 / 12.34 / 14.83 / 5.91; tools/indexed_sweep.py,
# PR 33), but a train step of two layers or more whose kernels raised their
# limit to 32 MiB never came back from the chip (PERF.md section 6, PR 33):
# these kernels ask for nothing.
BLOCKS_Q = (256, 128)
BLOCKS_K = (512, 256, 128)
MIN_SEQ = 512               # below it "auto" keeps the XLA tiles
HEAD_DIMS = (64, 128)


def pick_blocks(s: int):
    """``(block_q, block_k)`` for a sequence of ``s``: the largest of
    ``BLOCKS_Q`` and of ``BLOCKS_K`` that divide it (None: the kernels do not
    take ``s``)."""
    if s % _LANES:
        return None
    return (next(b for b in BLOCKS_Q if s % b == 0),
            next(b for b in BLOCKS_K if s % b == 0))


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


def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, dvec_ref,
               dq_ref, dq_scr, lse_scr, dvec_scr, *, group: int,
               head_dim: int, block_q: int, block_k: int, sm_scale: float):
    """dQ: the forward's grid. ``p = exp(s - L)`` again from the saved
    log-sum-exp, ``ds = p * (dO . v - D)``, ``dq += sm_scale * ds k``."""
    qb, kb, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        for t in range(group):
            lse_scr[t] = _column(lse_ref[t:t + 1, :], block_q)
            dvec_scr[t] = _column(dvec_ref[t:t + 1, :], block_q)

    @pl.when(_visited(qb, kb, block_q, block_k))
    def _accum():
        bias = _bias(mask_ref)
        k, v = k_ref[...], v_ref[...]
        for t in range(group):
            lanes = slice(t * head_dim, (t + 1) * head_dim)
            s = _scores(q_ref[:, lanes], k, sm_scale) + bias
            p = jnp.exp(s - _lanes(lse_scr[t], block_k))
            dp = _scores(do_ref[:, lanes], v, 1.0)
            ds = p * (dp - _lanes(dvec_scr[t], block_k))
            dq_scr[:, lanes] += jnp.dot(ds.astype(k.dtype), k,
                                        preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _finalize():
        dq_ref[...] = (sm_scale * dq_scr[...]).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, mask_t_ref, lse_ref, dvec_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, group: int, head_dim: int,
                block_q: int, block_k: int, sm_scale: float):
    """dK/dV: grid (batch, key head, k block, q block), q innermost, on
    TRANSPOSED scores ``k q^T [block_k, block_q]`` under the transposed mask
    tile: p^T and ds^T are the left operands of plain matmuls, the
    log-sum-exp and D broadcast along sublanes from their lane-dense rows,
    and the group's heads add up in one accumulator."""
    kb, qb, nq = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_visited(qb, kb, block_q, block_k))
    def _accum():
        bias = _bias(mask_t_ref)
        k, v = k_ref[...], v_ref[...]
        for t in range(group):
            lanes = slice(t * head_dim, (t + 1) * head_dim)
            q, do = q_ref[:, lanes], do_ref[:, lanes]
            st = _scores(k, q, sm_scale) + bias
            pt = jnp.exp(st - lse_ref[t:t + 1, :])
            dv_scr[...] += jnp.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dst = pt * (_scores(v, do, 1.0) - dvec_ref[t:t + 1, :])
            dk_scr[...] += jnp.dot(dst.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

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
    rows, the k-side tile, the mask tile and the transposed mask tile. A step
    the diagonal cuts off maps to the block of the nearest step that is
    visited, so nothing is fetched for it."""
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
    mask_t = pl.BlockSpec((None, bk, bq),
                          lambda *g: (g[0], *qk(g)[::-1]), **vmem)
    return qspec, qrow, kspec, mask, mask_t


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
    qspec, qrow, kspec, mspec, _ = _specs(group, dp, bq, bk, q_inner=False)
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
def _dq(q, k, v, mask, g, lse, dvec, sm_scale, bq, bk, interpret):
    """The operands, the output's cotangent ``g``, the saved ``lse`` and
    ``dvec = rowsum(g * out)`` (both ``[B,KV,H//KV,S]``) -> dq."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    (qv, kview, vv, gv), group, dp = _views(q, k, v, g)
    qspec, qrow, kspec, mspec, _ = _specs(group, dp, bq, bk, q_inner=False)
    dq = _call(
        functools.partial(_dq_kernel, group=group, head_dim=dp, block_q=bq,
                          block_k=bk, sm_scale=sm_scale),
        "indexed_dq", (b, kv, s // bq, s // bk),
        [qspec, kspec, kspec, mspec, qspec, qrow, qrow], qspec,
        jax.ShapeDtypeStruct(qv.shape, q.dtype),
        [pltpu.VMEM((bq, group * dp), jnp.float32),
         pltpu.VMEM((group, bq, _LANES), jnp.float32),
         pltpu.VMEM((group, bq, _LANES), jnp.float32)],
        interpret, (qv, kview, vv, mask, gv, lse, dvec))
    return _unview(dq, d, dp)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10))
def _dkv(q, k, v, mask, g, lse, dvec, sm_scale, bq, bk, interpret):
    """:func:`_dq`'s operands -> (dk, dv). The mask is transposed in HBM
    first, a pass over a byte a pair."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    (qv, kview, vv, gv), group, dp = _views(q, k, v, g)
    qspec, qrow, kspec, _, mspec_t = _specs(group, dp, bq, bk, q_inner=True)
    dk, dv = _call(
        functools.partial(_dkv_kernel, group=group, head_dim=dp, block_q=bq,
                          block_k=bk, sm_scale=sm_scale),
        "indexed_dkv", (b, kv, s // bk, s // bq),
        [kspec, kspec, qspec, qspec, mspec_t, qrow, qrow], [kspec, kspec],
        [jax.ShapeDtypeStruct(kview.shape, k.dtype),
         jax.ShapeDtypeStruct(vv.shape, v.dtype)],
        [pltpu.VMEM((bk, dp), jnp.float32), pltpu.VMEM((bk, dp), jnp.float32)],
        interpret, (kview, vv, qv, gv, jnp.swapaxes(mask, 1, 2), lse, dvec))
    return _unview(dk, d, dp), _unview(dv, d, dp)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attend(q, k, v, mask, bq, bk, interpret):
    return _attend_fwd(q, k, v, mask, bq, bk, interpret)[0]


def _attend_fwd(q, k, v, mask, bq, bk, interpret):
    out, lse = _forward(q, k, v, mask, _scale(q), bq, bk, interpret)
    # a block rematerialised whole keeps both (models/lm.py saves the name),
    # so its backward pass runs no forward kernel a second time
    out = checkpoint_name(out, "attention_out")
    lse = checkpoint_name(lse, "attention_out")
    return (out, lse), (q, k, v, mask, out, lse)


def _attend_bwd(bq, bk, interpret, residuals, cotangents):
    q, k, v, mask, out, lse = residuals
    g, _ = cotangents       # the log-sum-exp is a constant to its one reader
    dvec = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dvec = dvec.transpose(0, 2, 1).reshape(lse.shape)
    args = (q, k, v, mask, g, lse, dvec, _scale(q), bq, bk, interpret)
    return _dq(*args), *_dkv(*args), None


_attend.defvjp(_attend_fwd, _attend_bwd)


def attend_chosen(q, k, v, mask, *, block_q: int | None = None,
                  block_k: int | None = None, interpret: bool | None = None):
    """``q [B,S,H,D]``, ``k, v [B,S,KV,D]``, ``mask [B,S,S]`` int8 (1: the
    query attends to the key, 0: not; causal, and no row empty) -> the
    attention output ``[B,S,H,D]`` (differentiable in q, k, v) and the
    indexer's target ``sum_h p_h / H [B,S,S]`` float32, a constant to the
    gradient and defined only where the mask is set. Blocks default to
    ``pick_blocks(S)``."""
    s = q.shape[1]
    bq, bk = (block_q, block_k) if block_q and block_k else (
        pick_blocks(s) or (_LANES, _LANES))
    if s % bq or s % bk:
        raise ValueError(f"sequence {s} is no multiple of the kernels' "
                         f"blocks ({bq}, {bk})")
    if interpret is None:
        interpret = interpret_by_default()
    stop = jax.lax.stop_gradient
    mask = stop(mask)
    out, lse = _attend(q, k, v, mask, bq, bk, interpret)
    target = _target(stop(q), stop(k), mask, stop(lse), _scale(q), bq, bk,
                     interpret)
    return out, target
