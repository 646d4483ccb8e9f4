"""The passes of a hyper-connection over the stream state, fused.

A manifold-constrained hyper-connection (arXiv:2512.24880;
:class:`ddw_tpu.models.lm.HyperConnection`) reads a sublayer's input from ``n``
residual streams ``x [T, n C]`` (a token's streams side by side) and writes
the sublayer's output back to all of them. Written in ``jnp`` it passes the
state through HBM a dozen times a sublayer, several of them as float32 copies.
Here each side is one pass that keeps a block of tokens in VMEM, with the
backward pass written by hand:

- :func:`read` — ``z = rsqrt(mean(x^2) + eps) (x @ w^T) + bias`` (the
  flattened RMSNorm folded into the product: ``w`` holds the norm's gain, the
  projection and the three scalars), ``h = sum_n sigmoid(z[:, n]) x[n]``.
  One pass over ``x`` forward; backward one pass over ``x`` and ``dh`` that
  gives ``dx`` (the read's part, the product's and the norm's summed before
  anything is written) and the coefficients' cotangent, from which one XLA
  product over ``x`` makes ``dw`` and a sum ``dbias``.
- :func:`write` — ``x'[i] = sum_m res[i, m] x[m] + post[i] y``: reads ``x``
  and ``y``, writes ``x'``; backward one pass over ``dx'``, ``x``, ``y`` that
  gives ``dx``, ``dy`` and the coefficients' gradients.
- :func:`sinkhorn` — ``exp`` and the rounds of row and column normalisation
  with a token on a lane, every entry of the ``n x n`` matrix a full tile; the
  backward pass makes the rounds again in VMEM and walks them back.

Every sum and product is accumulated in float32 and rounded once where the
``jnp`` forms round (``h`` and ``x'`` to the streams' dtype). The product with
``w`` runs on the MXU with ``x`` as it is stored and ``w`` as a bfloat16 pair
(``w_hi + w_lo``), so that it carries ``w`` to 16 bits of mantissa: more than
the one-pass bfloat16 product XLA gives float32 operands at its default
precision. The backward products round the coefficients' cotangent to
bfloat16 (``dx``'s part, in the kernel) or carry it as such a pair (``dw``).

Pallas on the TPU, interpreted on the CPU (:mod:`ddw_tpu.ops.backend`). Blocks
are sized to stay inside the 16 MiB of VMEM a kernel gets unasked. The calls
are jitted one by one, so that an eager ``model.init`` traces and lowers each
kernel once and not once a sublayer.
:func:`fuses` says which stream states tile; everything else takes the ``jnp``
forms of ``models/lm.py``. The callers open the ``hyper_conn`` scope; the
backward functions open it themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ddw_tpu.ops.backend import interpret_by_default

F32 = jnp.float32
BF16 = jnp.bfloat16
LANES = 128
SCOPE = "hyper_conn"
# bytes of the streams a block of tokens may hold: the read side's forward
# holds two such blocks in VMEM (double buffering), its backward and the write
# side's forward four of half the size, the write side's backward six of a
# quarter
_READ_BYTES = 4 << 20
_PASS_BYTES = 2 << 20
_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_TN = (((0,), (0,)), ((), ()))      # a^T @ b


def _token_block(tokens: int, row_bytes: int, budget: int, cap: int) -> int:
    """The tokens a block of a pass holds: the largest of ``cap, cap/2, ...,
    16`` under ``budget`` bytes of the streams that divides ``tokens``; 0 if
    none does."""
    tb = cap
    while tb >= 16:
        if tb * row_bytes <= budget and tokens % tb == 0:
            return tb
        tb //= 2
    return 0


def fuses(x) -> bool:
    """Whether the stream state ``x [B, S, n, C]`` takes the fused passes:
    bfloat16 streams, ``C`` a multiple of the 128 lanes, ``B S`` a multiple of
    the 128-token block, and 16 tokens of the state inside the smallest
    budget a pass has."""
    if x.ndim != 4 or x.dtype != BF16:
        return False
    b, s, n, c = x.shape
    return (n > 1 and c % LANES == 0 and (b * s) % LANES == 0
            and 16 * n * c * 2 <= _PASS_BYTES // 2)


def _interpreted(interpret: bool | None) -> bool:
    """The interpreter on the CPU backend unless the caller says."""
    return interpret_by_default() if interpret is None else interpret


def _split(a):
    """``a`` in float32 as a bfloat16 pair ``hi + lo``."""
    hi = a.astype(BF16)
    return hi, (a - hi.astype(F32)).astype(BF16)


def _col(a, j: int):
    """Column ``j`` of ``a [rows, k]`` as ``[rows, 1]``."""
    return a[:, j:j + 1]


def _to_cols(cols, k: int):
    """``[rows, 1]`` columns side by side as ``[rows, k]``, zeros beyond."""
    rows = cols[0].shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    out = jnp.zeros((rows, k), F32)
    for j, col in enumerate(cols):
        out = jnp.where(lane == j, col, out)
    return out


def _of(m: int, c: int, cols: slice) -> slice:
    """The lane window ``cols`` of stream ``m`` in a row of streams ``c``
    wide each."""
    return slice(m * c + cols.start, m * c + cols.stop)


def _chunks(c: int, width: int):
    """Static lane windows of ``width`` (or what divides) over ``c``."""
    while c % width:
        width //= 2
    return [slice(at, at + width) for at in range(0, c, width)]


def _row(tb: int, width: int):
    """Block ``i`` of an array ``[T, width]``: ``tb`` tokens, every column."""
    return pl.BlockSpec((tb, width), lambda i: (i, 0))


def _whole(a):
    """All of a small array, the same for every block of tokens."""
    return pl.BlockSpec(a.shape, lambda i: (0, 0))


def _params():
    """Blocks of tokens are independent in every pass."""
    return pltpu.CompilerParams(dimension_semantics=("parallel",))


def _cost(arrays, flops: int):
    """What a call moves and computes, for the compiler's schedule."""
    return pl.CostEstimate(
        flops=flops, transcendentals=0,
        bytes_accessed=sum(a.size * a.dtype.itemsize for a in arrays))


# ---------------------------------------------------------------- read side


def _read_fwd_kernel(x_ref, w_ref, b_ref, h_ref, z_ref, r_ref, *, n: int,
                     eps: float):
    tb, d = x_ref.shape
    c, k = d // n, b_ref.shape[1]
    # a product a stream, independent of one another until they are summed
    both = sum(lax.dot_general(x_ref[:, at], w_ref[:, at], _NT,
                               preferred_element_type=F32)
               for at in _chunks(d, c))                     # [tb, 2k]
    ssq = jnp.zeros((tb, 1), F32)
    for m in range(n):
        xs = x_ref[:, m * c:(m + 1) * c].astype(F32)
        ssq += jnp.sum(xs * xs, axis=1, keepdims=True)
    r = lax.rsqrt(ssq * (1.0 / d) + eps)
    z = (both[:, :k] + both[:, k:]) * r + b_ref[...]
    z_ref[...] = z
    r_ref[...] = r
    pre = jax.nn.sigmoid(z)
    for cols in _chunks(c, 512):
        acc = _col(pre, 0) * x_ref[:, cols].astype(F32)
        for m in range(1, n):
            acc += _col(pre, m) * x_ref[:, _of(m, c, cols)].astype(F32)
        h_ref[:, cols] = acc.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _read_forward(x, w2, b, n: int, eps: float, interpret: bool):
    """``x [T, n C]``, ``w2 [2 k, n C]`` (the bfloat16 pair, one under the
    other), ``b [1, k]`` -> ``h [T, C]``, ``z [T, k]``, ``r [T, 1]``."""
    t, d = x.shape
    k = b.shape[1]
    tb = _token_block(t, d * x.dtype.itemsize, _READ_BYTES, 128)
    row = functools.partial(_row, tb)
    outs = [jax.ShapeDtypeStruct((t, d // n), x.dtype),
            jax.ShapeDtypeStruct((t, k), F32),
            jax.ShapeDtypeStruct((t, 1), F32)]
    return pl.pallas_call(
        functools.partial(_read_fwd_kernel, n=n, eps=eps),
        grid=(t // tb,),
        in_specs=[row(d), _whole(w2), _whole(b)],
        out_specs=[row(d // n), row(k), row(1)],
        out_shape=outs,
        compiler_params=_params(),
        cost_estimate=_cost(outs + [x, w2, b], 2 * t * d * (2 * k + 3)),
        interpret=interpret, name="hc_read_fwd",
    )(x, w2, b)


def _read_bwd_kernel(x_ref, w_ref, b_ref, z_ref, r_ref, dh_ref, dz_ref,
                     dx_ref, dzt_ref, *, n: int):
    tb, d = x_ref.shape
    c, k = d // n, b_ref.shape[1]
    z, r = z_ref[...], r_ref[...]
    pre = jax.nn.sigmoid(z)
    # the read's part of the coefficients' cotangent: dh . x[m], a stream each
    dots = [jnp.zeros((tb, 1), F32) for _ in range(n)]
    for cols in _chunks(c, 512):
        dh = dh_ref[:, cols].astype(F32)
        for m in range(n):
            dots[m] += jnp.sum(dh * x_ref[:, _of(m, c, cols)].astype(F32),
                               axis=1, keepdims=True)
    dzt = dz_ref[...] + _to_cols(dots, k) * pre * (1.0 - pre)
    dzt_ref[...] = dzt
    # z = r p + b with p = x @ w^T: dp = r dz, and r's own cotangent reaches
    # x through the mean of squares: (-sum(dz (z - b)) r^2 / d) x
    norm = (jnp.sum(dzt * (z - b_ref[...]), axis=1, keepdims=True)
            * r * r * (-1.0 / d))
    dp = (dzt * r).astype(BF16)

    for m in range(n):
        for cols in _chunks(c, 512):
            at = _of(m, c, cols)
            dx = (_col(pre, m) * dh_ref[:, cols].astype(F32)
                  + jnp.dot(dp, w_ref[:, at], preferred_element_type=F32)
                  + norm * x_ref[:, at].astype(F32))
            dx_ref[:, at] = dx.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _read_backward(x, w_hi, b, z, r, dh, dz, n: int, interpret: bool):
    """-> ``dx [T, n C]`` and the coefficients' whole cotangent ``[T, k]``,
    from which :func:`_read_bwd` makes ``dw`` and ``dbias``."""
    t, d = x.shape
    k = b.shape[1]
    tb = _token_block(t, d * x.dtype.itemsize, _PASS_BYTES, 64)
    row = functools.partial(_row, tb)
    outs = [jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((t, k), F32)]
    return pl.pallas_call(
        functools.partial(_read_bwd_kernel, n=n),
        grid=(t // tb,),
        in_specs=[row(d), _whole(w_hi), _whole(b), row(k), row(1),
                  row(d // n), row(k)],
        out_specs=[row(d), row(k)],
        out_shape=outs,
        compiler_params=_params(),
        cost_estimate=_cost(outs + [x, w_hi, z, dh, dz],
                            2 * t * d * (k + 6)),
        interpret=interpret, name="hc_read_bwd",
    )(x, w_hi, b, z, r, dh, dz)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _read(x, w, b, n, eps, interpret):
    return _read_fwd(x, w, b, n, eps, interpret)[0]


def _read_fwd(x, w, b, n, eps, interpret):
    hi, lo = _split(w)
    h, z, r = _read_forward(x, jnp.concatenate([hi, lo]), b, n, eps,
                            interpret)
    return (h, z), (x, hi, b, z, r)


def _read_bwd(n, eps, interpret, residuals, cotangents):
    x, hi, b, z, r = residuals
    with jax.named_scope(SCOPE):
        dx, dzt = _read_backward(x, hi, b, z, r, *cotangents, n, interpret)
        # dw = (r dz)^T x, the cotangent carried as a bfloat16 pair, by XLA:
        # one more pass over x. A kernel that gathered it over its blocks of
        # tokens (an output revisited, the grid "arbitrary") cost the
        # compiled step of xing4_train_s4096 3.4 GB of temporaries
        k = dzt.shape[1]
        both = lax.dot_general(jnp.concatenate(_split(dzt * r), axis=1), x,
                               _TN, preferred_element_type=F32)
        return dx, both[:k] + both[k:], jnp.sum(dzt, axis=0, keepdims=True)


_read.defvjp(_read_fwd, _read_bwd)


def read(x, w, bias, eps: float, interpret: bool | None = None):
    """A sublayer's input and its hyper-connection's coefficients in one pass
    over the streams ``x [B, S, n, C]``: ``z = rsqrt(mean(vec(x)^2) + eps)
    (vec(x) @ w^T) + bias`` (``w [k, n C]`` float32 with the flattened norm's
    gain and the three scalars folded in, ``bias [k]``) and ``h = sum_n
    sigmoid(z[..., n]) x[:, :, n]``. Returns ``h [B, S, C]`` in x's dtype and
    ``z [B, S, k]`` in float32. Kept for the backward pass: ``x``, ``w``'s
    leading bfloat16 half, ``z`` and the norm's ``rsqrt`` (4 bytes a token)."""
    bsz, s, n, c = x.shape
    h, z = _read(x.reshape(bsz * s, n * c), w, bias[None, :], n, eps,
                 _interpreted(interpret))
    return h.reshape(bsz, s, c), z.reshape(bsz, s, -1)


# --------------------------------------------------------------- write side


def _write_fwd_kernel(x_ref, y_ref, c_ref, o_ref, *, n: int):
    c = y_ref.shape[1]
    coef = c_ref[...]
    for cols in _chunks(c, 256):
        xs = [x_ref[:, _of(m, c, cols)].astype(F32) for m in range(n)]
        y = y_ref[:, cols].astype(F32)
        for i in range(n):
            acc = _col(coef, i) * y
            for m in range(n):
                acc += _col(coef, n + i * n + m) * xs[m]
            o_ref[:, _of(i, c, cols)] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _write_forward(x, y, coef, n: int, interpret: bool):
    """``x [T, n C]``, ``y [T, C]``, ``coef [T, n + n n]`` (post, then res by
    rows) -> ``x' [T, n C]``."""
    t, d = x.shape
    tb = _token_block(t, d * x.dtype.itemsize, _PASS_BYTES, 64)
    row = functools.partial(_row, tb)
    return pl.pallas_call(
        functools.partial(_write_fwd_kernel, n=n),
        grid=(t // tb,),
        in_specs=[row(d), row(d // n), row(coef.shape[1])],
        out_specs=row(d),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        compiler_params=_params(),
        cost_estimate=_cost([x, x, y, coef], 2 * t * d * (n + 1)),
        interpret=interpret, name="hc_write_fwd",
    )(x, y, coef)


def _write_bwd_kernel(x_ref, y_ref, c_ref, g_ref, dx_ref, dy_ref, dc_ref, *,
                      n: int):
    tb, c = y_ref.shape
    coef = c_ref[...]
    sums = [jnp.zeros((tb, LANES), F32) for _ in range(n + n * n)]
    for cols in _chunks(c, LANES):
        gs = [g_ref[:, _of(i, c, cols)].astype(F32) for i in range(n)]
        xs = [x_ref[:, _of(m, c, cols)].astype(F32) for m in range(n)]
        y = y_ref[:, cols].astype(F32)
        dy = _col(coef, 0) * gs[0]
        for i in range(1, n):
            dy += _col(coef, i) * gs[i]
        dy_ref[:, cols] = dy.astype(dy_ref.dtype)
        for m in range(n):
            dx = _col(coef, n + m) * gs[0]
            for i in range(1, n):
                dx += _col(coef, n + i * n + m) * gs[i]
            dx_ref[:, _of(m, c, cols)] = dx.astype(dx_ref.dtype)
        for i in range(n):
            sums[i] += gs[i] * y
            for m in range(n):
                sums[n + i * n + m] += gs[i] * xs[m]
    dc_ref[...] = _to_cols([jnp.sum(a, axis=1, keepdims=True) for a in sums],
                           dc_ref.shape[1])


@functools.partial(jax.jit, static_argnums=(4, 5))
def _write_backward(x, y, coef, g, n: int, interpret: bool):
    t, d = x.shape
    tb = _token_block(t, d * x.dtype.itemsize, _PASS_BYTES // 2, 32)
    row = functools.partial(_row, tb)
    k = coef.shape[1]
    outs = [jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((t, d // n), y.dtype),
            jax.ShapeDtypeStruct((t, k), F32)]
    return pl.pallas_call(
        functools.partial(_write_bwd_kernel, n=n),
        grid=(t // tb,),
        in_specs=[row(d), row(d // n), row(k), row(d)],
        out_specs=[row(d), row(d // n), row(k)],
        out_shape=outs,
        compiler_params=_params(),
        cost_estimate=_cost(outs + [x, y, coef, g], 2 * t * d * (2 * n + 2)),
        interpret=interpret, name="hc_write_bwd",
    )(x, y, coef, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _write(x, y, coef, n, interpret):
    return _write_forward(x, y, coef, n, interpret)


def _write_fwd(x, y, coef, n, interpret):
    return _write_forward(x, y, coef, n, interpret), (x, y, coef)


def _write_bwd(n, interpret, residuals, g):
    with jax.named_scope(SCOPE):
        return _write_backward(*residuals, g, n, interpret)


_write.defvjp(_write_fwd, _write_bwd)


def write(x, y, h_post, h_res, interpret: bool | None = None):
    """The streams after a sublayer in one pass: ``x'[:, :, i] = sum_m
    h_res[..., i, m] x[:, :, m] + h_post[..., i] y`` for ``x [B, S, n, C]``,
    ``y [B, S, C]``, ``h_post [B, S, n]``, ``h_res [B, S, n, n]`` (float32),
    accumulated in float32 and rounded once to x's dtype. Kept for the
    backward pass: ``x``, ``y`` and the coefficients."""
    bsz, s, n, c = x.shape
    t = bsz * s
    coef = jnp.concatenate([h_post.reshape(t, n).astype(F32),
                            h_res.reshape(t, n * n).astype(F32)], axis=1)
    out = _write(x.reshape(t, n * c), y.reshape(t, c).astype(x.dtype), coef,
                 n, _interpreted(interpret))
    return out.reshape(x.shape)


# ----------------------------------------------------------------- sinkhorn


def _round(m, by_rows: bool, eps: float):
    """One half of a round on ``m[i][j]``: every row (or column) over its sum
    + ``eps``. Returns the matrix and the inverse sums."""
    n = len(m)
    lines = m if by_rows else [[m[i][j] for i in range(n)] for j in range(n)]
    inv = [1.0 / (sum(line[1:], line[0]) + eps) for line in lines]
    return [[m[i][j] * inv[i if by_rows else j] for j in range(n)]
            for i in range(n)], inv


def _entries(ref, n: int, *at):
    """The matrix a ref holds entry by entry at ``ref[at]``, as ``m[i][j]``."""
    return [[ref[at + (i * n + j,)] for j in range(n)] for i in range(n)]


def _put(ref, m, *at):
    for i, line in enumerate(m):
        for j, entry in enumerate(line):
            ref[at + (i * len(m) + j,)] = entry


def _sinkhorn_fwd_kernel(l_ref, m_ref, *, n: int, iters: int, eps: float):
    def one(_, m):
        return _round(_round(m, True, eps)[0], False, eps)[0]

    m = [[jnp.exp(entry) for entry in line] for line in _entries(l_ref, n)]
    _put(m_ref, lax.fori_loop(0, iters, one, m))


def _sinkhorn_bwd_kernel(l_ref, g_ref, dl_ref, m_scr, inv_scr, *, n: int,
                         iters: int, eps: float):
    """The rounds made again, every half round's matrix and inverse sums kept
    in VMEM (``m_scr[t]`` is what half round ``t`` started from), then walked
    back: ``m_out = m_in inv`` gives ``dm_in = inv (dm_out - sum over the
    line of dm_out m_out)``."""
    def forth(t, m):
        for half, by_rows in enumerate((True, False)):
            _put(m_scr, m, 2 * t + half)
            m, inv = _round(m, by_rows, eps)
            for a in range(n):
                inv_scr[2 * t + half, a] = inv[a]
        return m

    def back(t, carry):
        g, m = carry
        for half, by_rows in ((1, False), (0, True)):
            at = 2 * (iters - 1 - t) + half
            line = [[i if by_rows else j for j in range(n)] for i in range(n)]
            dot = [0.0] * n
            for i in range(n):
                for j in range(n):
                    dot[line[i][j]] = dot[line[i][j]] + g[i][j] * m[i][j]
            g = [[(g[i][j] - dot[line[i][j]]) * inv_scr[at, line[i][j]]
                  for j in range(n)] for i in range(n)]
            m = _entries(m_scr, n, at)
        return g, m

    m = [[jnp.exp(entry) for entry in line] for line in _entries(l_ref, n)]
    last = lax.fori_loop(0, iters, forth, m)
    g, first = lax.fori_loop(0, iters, back, (_entries(g_ref, n), last))
    _put(dl_ref, [[g[i][j] * first[i][j] for j in range(n)]
                  for i in range(n)])


@functools.partial(jax.jit, static_argnums=tuple(range(6)))
def _sinkhorn_call(kernel, n, iters, eps, interpret, name, *args):
    entries, groups, _ = args[0].shape
    rows = 8 if groups % 8 == 0 else groups
    spec = pl.BlockSpec((entries, rows, LANES), lambda i: (0, i, 0))
    kept = [] if len(args) == 1 else [      # the backward pass's rounds
        pltpu.VMEM((2 * iters, entries, rows, LANES), F32),
        pltpu.VMEM((2 * iters, n, rows, LANES), F32)]
    return pl.pallas_call(
        functools.partial(kernel, n=n, iters=iters, eps=eps),
        grid=(groups // rows,),
        in_specs=[spec] * len(args), out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(args[0].shape, F32),
        scratch_shapes=kept,
        compiler_params=_params(),
        interpret=interpret, name=name,
    )(*args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _sinkhorn(logits, n, iters, eps, interpret):
    return _sinkhorn_call(_sinkhorn_fwd_kernel, n, iters, eps, interpret,
                          "hc_sinkhorn_fwd", logits)


def _sinkhorn_fwd(logits, n, iters, eps, interpret):
    return _sinkhorn(logits, n, iters, eps, interpret), logits


def _sinkhorn_bwd(n, iters, eps, interpret, logits, g):
    with jax.named_scope(SCOPE):
        return (_sinkhorn_call(_sinkhorn_bwd_kernel, n, iters, eps, interpret,
                               "hc_sinkhorn_bwd", logits, g),)


_sinkhorn.defvjp(_sinkhorn_fwd, _sinkhorn_bwd)


def sinkhorn_tiles(logits) -> bool:
    """Whether ``logits [..., n, n]`` take :func:`sinkhorn`: float32, the
    tokens before the matrix a multiple of the 128 lanes."""
    tokens = 1
    for size in logits.shape[:-2]:
        tokens *= size
    return (logits.ndim > 2 and logits.dtype == F32
            and logits.shape[-1] == logits.shape[-2] and tokens % LANES == 0)


def sinkhorn(logits, iters: int, eps: float, interpret: bool | None = None):
    """``exp(logits) [..., n, n]`` made doubly stochastic by ``iters`` rounds
    of (each row over its sum + ``eps``, then each column), a token on a lane:
    the matrix's ``n n`` entries are laid out ``[n n, tokens / 128, 128]``
    around the kernel, so that no tensor with the matrix as its two minor
    dimensions is computed on. The backward pass keeps the logits and makes
    the rounds again."""
    n = logits.shape[-1]
    lanes = jnp.moveaxis(logits.reshape(-1, n * n), 0, 1).reshape(
        n * n, -1, LANES)
    out = _sinkhorn(lanes, n, iters, eps, _interpreted(interpret))
    return jnp.moveaxis(out.reshape(n * n, -1), 0, 1).reshape(logits.shape)
