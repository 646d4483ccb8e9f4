"""Decoder-only transformer LM — the long-context model family.

The reference stack has no language model and no attention (SURVEY.md §5
"Long-context ... Absent"); this family exists because long-context and model
sharding are first-class axes of this framework, not parity items. The same
module runs three ways off one definition:

- single device: causal flash attention (:mod:`ddw_tpu.ops.flash_attention`);
- sequence parallel: construct with ``seq_axis='seq'`` and call inside
  ``shard_map`` with tokens sharded on the sequence dim — attention becomes
  ring attention (K/V shards rotating by ``ppermute``,
  :mod:`ddw_tpu.parallel.ring_attention`) and position embeddings are sliced at
  the shard's global offset (``lax.axis_index * S_local``);
- tensor parallel: submodule names (``attn/{query,key,value,out}``,
  ``mlp/{fc1,fc2}``) match :data:`ddw_tpu.parallel.sharding.LM_TP_RULES`, so the
  GSPMD path shards heads/MLP over the ``model`` axis with no model changes.

Pre-LN blocks, learned positional embeddings, weight-untied vocab head. What a
layer is made of beyond that — RMSNorm, a gated MLP, heads wider than
``hidden / num_heads``, normed queries and keys, multi-axis RoPE, attention
over keys an indexer chose, latent attention (low-rank q and kv paths, one
shared rotary key head, value heads narrower than q/k heads), routed experts
on a chip's share, a residual path of several hyper-connected streams — is one
:class:`ddw_tpu.utils.config.LayerSpec` handed from the model to its blocks.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

from ddw_tpu.ops import hyper_connection
from ddw_tpu.ops.flash_attention import flash_mha_seq_major
from ddw_tpu.parallel.ring_attention import ring_attention
from ddw_tpu.utils.config import LayerSpec


def layer_norm(spec: LayerSpec, name: str | None = None):
    """The spec's norm, computed and returned in float32."""
    if spec.norm == "layernorm":
        return nn.LayerNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                            name=name)
    if spec.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                          name=name)
    raise ValueError(f"unknown norm {spec.norm!r}; use 'layernorm' or "
                     f"'rmsnorm'")


def yarn_of(spec: LayerSpec) -> tuple:
    """``apply_rope``'s ``yarn`` argument for the spec's rotary scaling: its
    four numbers, or nothing."""
    if spec.rope_scaling not in ("", "yarn"):
        raise ValueError(f"unknown rope_scaling {spec.rope_scaling!r}; use "
                         f"'' or 'yarn'")
    return ((spec.rope_factor, spec.rope_beta_fast, spec.rope_beta_slow,
             spec.rope_original_len) if spec.rope_scaling else ())


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits) [..., n, n]`` made doubly stochastic by ``iters`` rounds
    of (each row divided by its sum + ``eps``, then each column). Float32
    logits of a multiple of 128 tokens take the kernel of
    :mod:`ddw_tpu.ops.hyper_connection` (a token on a lane, the rounds made
    again for the backward pass); anything else the loop below."""
    if hyper_connection.sinkhorn_tiles(logits):
        return hyper_connection.sinkhorn(logits, iters, eps)
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hyper_read(x, h_pre):
    """A sublayer's input: the streams ``x [B, S, n, C]`` mixed by ``h_pre
    [B, S, n]`` -> ``[B, S, C]`` in x's dtype. The ``jnp`` form: where the
    streams tile, :class:`HyperConnection` reads in the pass that makes the
    coefficients, and this is what the tests hold that pass to."""
    with jax.named_scope("hyper_conn"):
        return jnp.einsum("bsn,bsnc->bsc", h_pre,
                          x.astype(jnp.float32)).astype(x.dtype)


def hyper_write(x, y, h_post, h_res):
    """The streams after a sublayer: ``h_res x + h_post^T y`` — the streams
    mixed by ``h_res [B, S, n, n]`` and the sublayer's output ``y [B, S, C]``
    written to each by ``h_post [B, S, n]``. Streams that tile
    (:func:`ddw_tpu.ops.hyper_connection.fuses`) take its one pass, which
    keeps ``x``, ``y`` and the coefficients for a backward pass of its own;
    anything else the ``jnp`` form below, the tests' reference."""
    with jax.named_scope("hyper_conn"):
        if hyper_connection.fuses(x) and y.dtype == x.dtype:
            return hyper_connection.write(x, y, h_post, h_res)
        mixed = jnp.einsum("bsnm,bsmc->bsnc", h_res, x.astype(jnp.float32))
        return (mixed + h_post[..., None]
                * y.astype(jnp.float32)[:, :, None, :]).astype(x.dtype)


class _Gain(nn.Module):
    """The gain of an ``nn.RMSNorm`` under its name (``scale``), for a pass
    that folds it into a product instead of applying the norm."""

    @nn.compact
    def __call__(self, width: int):
        return self.param("scale", nn.initializers.ones, (width,),
                          jnp.float32)


class HyperConnection(nn.Module):
    """A manifold-constrained hyper-connection (arXiv:2512.24880) around one
    sublayer: its coefficients from the stream state ``x [B, S, n, C]`` of
    each token, in float32, and the sublayer's input read with them:

        xt = RMSNorm(vec(x))                        over all n C numbers, gain
        [Hpre~ | Hpost~ | Hres~] = alpha * (xt phi) + bias     n | n | n x n
        Hpre = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
        Hres = sinkhorn(clip(Hres~ + hyper_res_diag I))
        h = sum_n Hpre[n] x[n]

    Returns ``(h, h_post, h_res)``: the input ``[B, S, C]`` in x's dtype, and
    what :func:`hyper_write` takes. Streams that tile
    (:func:`ddw_tpu.ops.hyper_connection.fuses`: bfloat16, ``C`` a multiple of
    128, ``B S`` a multiple of 128) make ``xt phi`` and ``h`` in ONE pass over
    ``x`` (``RMSNorm(x) @ phi = rsqrt(mean(x^2) + eps) (x @ (g * phi))``: no
    float32 copy of the state leaves VMEM), whose backward pass keeps ``x``,
    the folded weight's bfloat16 half, the ``n (n + 2)`` numbers a token
    before the sigmoids and the norm's ``rsqrt``; anything else takes the
    ``jnp`` forms (:func:`hyper_read`), which are also what the tests hold
    the fused pass to. Sows the counters ``hc_res_offdiag_share`` (the mass of
    ``Hres`` off its diagonal over ``n``, a mean over tokens: 0 is a plain
    residual, ``1 - 1/n`` streams mixed evenly), ``hc_sinkhorn_error`` (the
    largest ``|row sum - 1|`` or ``|column sum - 1|`` after the last round)
    and ``hc_fused_share`` (1 where this sublayer took the fused passes, else
    0). Nothing of it is kept across a block's rematerialisation."""

    layer: LayerSpec

    @nn.compact
    def __call__(self, x):
        spec = self.layer
        b, s, n, c = x.shape
        fused = hyper_connection.fuses(x)
        with jax.named_scope("hyper_conn"):
            phi = self.param("phi", nn.initializers.normal(0.02),
                             (n * c, n * (n + 2)), jnp.float32)
            alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                               jnp.float32)
            bias = self.param("bias", nn.initializers.zeros, (n * (n + 2),),
                              jnp.float32)
            gain = jnp.repeat(alpha, jnp.asarray([n, n, n * n]),
                              total_repeat_length=n * (n + 2))
            if fused:
                scale = _Gain(name="norm")(n * c)
                h, coef = hyper_connection.read(
                    x, (phi * scale[:, None] * gain).T, bias, spec.norm_eps)
                pre, post, res = jnp.split(coef, [n, 2 * n], axis=-1)
            else:
                xt = nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                                name="norm")(x.reshape(b, s, n * c))
                raw = xt @ phi                              # [B, S, n(n+2)]
                pre, post, res = jnp.split(raw * gain + bias, [n, 2 * n],
                                           axis=-1)
                h = hyper_read(x, jax.nn.sigmoid(pre))
            res = res.reshape(b, s, n, n)
            if spec.hyper_res_diag:
                res = res + spec.hyper_res_diag * jnp.eye(n, dtype=res.dtype)
            h_res = sinkhorn(jnp.clip(res, *spec.hyper_res_clamp),
                             spec.hyper_sinkhorn_iters, spec.hyper_eps)
            seen = lax.stop_gradient(h_res)
            sums = jnp.concatenate([jnp.sum(seen, -1), jnp.sum(seen, -2)], -1)
            self.sow("intermediates", "counters", {
                "hc_res_offdiag_share": jnp.mean(
                    1.0 - jnp.trace(seen, axis1=-2, axis2=-1) / n),
                "hc_sinkhorn_error": jnp.max(jnp.abs(sums - 1.0)),
                "hc_fused_share": jnp.float32(fused)})
            return h, 2.0 * jax.nn.sigmoid(post), h_res


class CausalSelfAttention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    seq_axis: str | None = None
    decode: bool = False     # autoregressive mode: KV cache, one token per call
    max_len: int = 2048      # cache capacity in decode mode
    slot_decode: bool = False  # continuous-batching mode: the cache batch dim
                             # is a pool of serving slots, each at its OWN
                             # depth — cache_index becomes a [B] vector, K/V
                             # writes scatter per row, and masking/overflow
                             # go per-row (ddw_tpu.serve.slots). S must be 1.
    paged_decode: bool = False  # paged continuous batching: K/V live in a
                             # GLOBAL pool of kv_cache_blocks fixed-size
                             # blocks instead of per-row contiguous strips;
                             # each call takes per-row block tables (gather
                             # indices) and start positions as ARGUMENTS, so
                             # the cache tree is batch-independent — one pool
                             # serves prefill groups and the decode batch
                             # alike (ddw_tpu.serve.blocks). Any S works
                             # (S>1 = chunked/suffix prefill into blocks;
                             # speculative verify rides this same path — one
                             # S=k+1 call scores a row's draft block with
                             # intra-block causality, BlockPool.spec_verify).
    kv_cache_blocks: int = 0  # paged mode: usable blocks + 1 null block
    kv_block_size: int = 0   # paged mode: tokens per block; must divide the
                             # attention tile so the gathered view is laid
                             # out exactly like the contiguous cache (that
                             # layout equality is what makes paged outputs
                             # bit-identical to the sequential path)
    num_kv_heads: int = 0    # GQA (Ainslie et al. 2305.13245): 0 = num_heads
                             # (MHA); fewer KV heads shrink the k/v params and
                             # the decode cache by H/KV; K/V broadcast to the
                             # full head count at compute time
    lora_rank: int = 0       # >0: rank-r adapters on lora_targets projections
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")
    layer: LayerSpec = LayerSpec()

    @nn.compact
    def __call__(self, x, positions=None, block_tables=None, start_pos=None,
                 adapters=None):
        from ddw_tpu.models.lora import maybe_lora_dense, row_lora_delta

        spec = self.layer
        if spec.attention == "latent":
            return self._latent(x, positions)
        b, s, d = x.shape
        head_dim = spec.head_dim or d // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        if self.num_heads % kv_heads:
            raise ValueError(f"num_heads {self.num_heads} not divisible by "
                             f"num_kv_heads {kv_heads}")
        groups = self.num_heads // kv_heads

        def dense(name, heads=self.num_heads):
            return maybe_lora_dense((heads, head_dim), name,
                                    rank=self.lora_rank, alpha=self.lora_alpha,
                                    targets=self.lora_targets, dtype=self.dtype,
                                    use_bias=spec.bias)

        def with_delta(name, y, x_in, cn=1):
            # hot-swapped per-row adapter delta (serving path); the delta is
            # added where LoRADenseGeneral would add a trained one — before
            # RoPE and before the cache write
            ab = (adapters or {}).get(name)
            if ab is None:
                return y
            return y + row_lora_delta(x_in, ab[0], ab[1], cn).astype(y.dtype)

        # the projections with their norms and rotary turn: one layer scope
        # of the compiled step (obs/step_scopes.py), metadata only
        with jax.named_scope("attn_proj"):
            q = with_delta("query", dense("query")(x), x)       # [B, S, H, hd]
            k = with_delta("key", dense("key", kv_heads)(x), x)  # [B,S,KV,hd]
            v = with_delta("value", dense("value", kv_heads)(x), x)
            if spec.qk_norm:
                # RMSNorm over each head's own dims, one gain shared by the
                # heads
                q = nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                               name="q_norm")(q)
                k = nn.RMSNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                               name="k_norm")(k)
            if positions is not None:
                # RoPE: rotate q/k by ABSOLUTE position before any cache write
                # or ring hop — scores then depend only on relative distance,
                # so the cached/ring-shipped K needs no further position
                # plumbing.
                from ddw_tpu.ops.rope import apply_rope

                rope_at = positions
                if spec.mrope_section:
                    # token rows: the position components (temporal, height,
                    # width) of a text token are all its index
                    rope_at = jnp.broadcast_to(
                        positions, (len(spec.mrope_section), *positions.shape))
                rope_kw = dict(seq_axis=1, theta=spec.rope_theta,
                               sections=spec.mrope_section,
                               yarn=yarn_of(spec))
                q = apply_rope(q, rope_at, **rope_kw).astype(self.dtype)
                k = apply_rope(k, rope_at, **rope_kw).astype(self.dtype)
        if spec.attention not in ("full", "indexed"):
            raise ValueError(f"unknown attention {spec.attention!r}; use "
                             f"'full', 'indexed' or 'latent'")
        if spec.attention == "indexed" and (
                self.decode or self.seq_axis is not None or positions is None):
            raise NotImplementedError(
                "indexed attention trains on one device's whole sequence "
                "with RoPE; a cache for the indexer's keys (serving) and a "
                "ring over its scores are not written (ROADMAP M8)")

        if self.decode:
            # KV cache: accepts S tokens per call (S>1 = batched prefill, S=1 =
            # per-token decode). Attention runs TILED over the cache with
            # online softmax, and tiles past the filled position are skipped at
            # runtime (lax.cond) — per-token cost scales with the generated
            # length in TILE-sized increments instead of paying O(max_len)
            # every call (VERDICT r1 weak #4). Writes past max_len poison the
            # output with NaN (loud failure) instead of silently clamping.
            tile = min(256, self.max_len)
            cap = -(-self.max_len // tile) * tile  # capacity, tile multiple
            # GQA: the cache holds KV heads only — the H/KV memory saving is
            # exactly what grouped queries exist for at generation time
            if self.slot_decode and s != 1:
                raise ValueError(f"slot_decode processes one token per slot "
                                 f"per call, got S={s}")
            # cumulative count of KV tiles actually computed — observability
            # hook proving the skip logic works (test_lm pins it); costs one
            # scalar add per call.
            tiles = self.variable("cache", "tiles_computed",
                                  lambda: jnp.zeros((), jnp.int32))
            if self.paged_decode:
                # Paged KV (vLLM lineage, arXiv 2309.06180): the cache is a
                # GLOBAL pool of fixed-size blocks, and this row's K/V lives
                # wherever its block table points. The table is padded to
                # cap // block_size entries (unallocated tail -> block 0,
                # the reserved null block), so gathering blocks back in
                # table order reconstructs EXACTLY the contiguous [cap]
                # layout — the tile loop below then runs unchanged on the
                # gathered view, which is what keeps paged decode
                # bit-identical to the contiguous path.
                bs = self.kv_block_size
                if bs < 1 or tile % bs:
                    raise ValueError(
                        f"kv_block_size {bs} must be >= 1 and divide the "
                        f"attention tile {tile}")
                if self.kv_cache_blocks < 2:
                    raise ValueError("paged_decode needs kv_cache_blocks >= 2"
                                     " (block 0 is the reserved null block)")
                n_tbl = cap // bs
                if start_pos is None:
                    start_pos = jnp.zeros((b,), jnp.int32)
                if block_tables is None:
                    block_tables = jnp.zeros((b, n_tbl), jnp.int32)
                ck = self.variable("cache", "kv_block_key", jnp.zeros,
                                   (self.kv_cache_blocks, bs, kv_heads,
                                    head_dim), k.dtype)
                cv = self.variable("cache", "kv_block_value", jnp.zeros,
                                   (self.kv_cache_blocks, bs, kv_heads,
                                    head_dim), v.dtype)
                pos = start_pos                       # [B] per-row depths
                p = pos[:, None] + jnp.arange(s)      # [B, S] write positions
                # out-of-capacity writes (a finished row's chain overshoot)
                # are routed to the null block instead of clamp-corrupting a
                # real one; unallocated table entries are already 0
                safe = p < cap
                entry = jnp.take_along_axis(
                    block_tables, jnp.clip(p // bs, 0, n_tbl - 1), axis=1)
                bt = jnp.where(safe, entry, 0)
                off = jnp.where(safe, p % bs, 0)
                ck.value = ck.value.at[bt, off].set(k)
                cv.value = cv.value.at[bt, off].set(v)
                # gather-back: [B, n_tbl, bs, ...] -> contiguous [B, cap, ...]
                src_k = ck.value[block_tables].reshape(
                    b, cap, kv_heads, head_dim)
                src_v = cv.value[block_tables].reshape(
                    b, cap, kv_heads, head_dim)
            else:
                ck = self.variable("cache", "cached_key", jnp.zeros,
                                   (b, cap, kv_heads, head_dim), k.dtype)
                cv = self.variable("cache", "cached_value", jnp.zeros,
                                   (b, cap, kv_heads, head_dim), v.dtype)
                idx = self.variable(
                    "cache", "cache_index",
                    lambda: jnp.zeros((b,) if self.slot_decode else (),
                                      jnp.int32))
                pos = idx.value
                if self.slot_decode:
                    # per-row write: each slot appends at its own depth
                    row_write = jax.vmap(
                        lambda c, t, p: lax.dynamic_update_slice(
                            c, t, (p, 0, 0)))
                    ck.value = row_write(ck.value, k, pos)
                    cv.value = row_write(cv.value, v, pos)
                else:
                    ck.value = lax.dynamic_update_slice(
                        ck.value, k, (0, pos, 0, 0))
                    cv.value = lax.dynamic_update_slice(
                        cv.value, v, (0, pos, 0, 0))
                idx.value = pos + s
                src_k, src_v = ck.value, cv.value

            q32 = (q.astype(jnp.float32) / float(head_dim) ** 0.5
                   ).transpose(0, 2, 1, 3)          # [B, H, S, hd]
            if self.slot_decode or self.paged_decode:
                qpos = pos[:, None] + jnp.arange(s)  # [B, S] per-row positions
                last = jnp.max(pos) + s - 1          # deepest filled position
            else:
                qpos = pos + jnp.arange(s)          # [S] global query positions
                last = pos + s - 1                  # newest filled position
            # [B, S]: rows shallower than a tile mask it out entirely — the
            # masked tile's (m, l, o) update is an exact no-op (m carries over,
            # exp underflows to 0), so per-row results match a per-row skip.
            qpos_b = qpos if qpos.ndim == 2 else qpos[None]

            def tile_body(carry, t):
                start = t * tile

                def active(c):
                    m, l, o, cnt = c
                    k_t = lax.dynamic_slice_in_dim(
                        src_k, start, tile, axis=1).astype(jnp.float32)
                    v_t = lax.dynamic_slice_in_dim(
                        src_v, start, tile, axis=1).astype(jnp.float32)
                    if groups > 1:  # broadcast KV heads over their query group
                        k_t = jnp.repeat(k_t, groups, axis=2)
                        v_t = jnp.repeat(v_t, groups, axis=2)
                    s_t = jnp.einsum("bhqd,bkhd->bhqk", q32, k_t)  # [B,H,S,T]
                    kpos = start + jnp.arange(tile)
                    mask = kpos[None, None, None, :] <= qpos_b[:, None, :, None]
                    s_t = jnp.where(mask, s_t, -1e30)
                    m_new = jnp.maximum(m, s_t.max(-1))
                    p = jnp.exp(s_t - m_new[..., None])
                    scale = jnp.exp(m - m_new)
                    l_new = l * scale + p.sum(-1)
                    o_new = (o * scale[..., None]
                             + jnp.einsum("bhqk,bkhd->bhqd", p, v_t))
                    return m_new, l_new, o_new, cnt + 1

                return lax.cond(start <= last, active, lambda c: c, carry), None

            m0 = jnp.full((b, self.num_heads, s), -1e30, jnp.float32)
            l0 = jnp.zeros((b, self.num_heads, s), jnp.float32)
            o0 = jnp.zeros((b, self.num_heads, s, head_dim), jnp.float32)
            (m_f, l_f, o_f, n_tiles), _ = lax.scan(
                tile_body, (m0, l0, o0, jnp.zeros((), jnp.int32)),
                jnp.arange(cap // tile))
            tiles.value = tiles.value + n_tiles
            out = (o_f / l_f[..., None]).transpose(0, 2, 1, 3)  # [B,S,H,hd]
            # Hard failure on overflow: a write past max_len would have
            # clamp-overwritten the last cache rows; NaN-poison the result so
            # the caller cannot miss it (host-side raise is not possible for a
            # traced index). In slot mode only the overflowing ROW is poisoned
            # — other slots keep decoding.
            if self.paged_decode:
                # per-QUERY poison: a suffix prefill's padded bucket may
                # overshoot max_len while every real query is in range —
                # only the out-of-range (pad, discarded) queries go NaN
                overflow = (qpos >= self.max_len)[:, :, None, None]
            else:
                overflow = (pos + s) > self.max_len
                if self.slot_decode:
                    overflow = overflow[:, None, None, None]
            out = jnp.where(overflow, jnp.nan, out).astype(x.dtype)
        elif spec.attention == "indexed":
            from ddw_tpu.ops.indexed_attention import indexed_attention
            from ddw_tpu.ops.rope import apply_rope

            # the indexer reads the normed hidden state as a constant: its
            # three matrices learn from the KL term alone. Float32 at full
            # precision, scores included: they decide which keys are seen.
            with jax.named_scope("indexer"):
                seen = lax.stop_gradient(x).astype(jnp.float32)
                proj = dict(use_bias=False, dtype=jnp.float32,
                            precision=lax.Precision.HIGHEST)
                qi = nn.DenseGeneral((spec.index_heads, spec.index_head_dim),
                                     name="index_q", **proj)(seen)
                ki = nn.LayerNorm(epsilon=spec.norm_eps, dtype=jnp.float32,
                                  name="index_k_norm")(
                    nn.Dense(spec.index_head_dim, name="index_k",
                             **proj)(seen))
                wi = nn.Dense(spec.index_heads, name="index_w", **proj)(seen)
                qi = apply_rope(qi, positions, seq_axis=1,
                                theta=spec.rope_theta)
                ki = apply_rope(ki[:, :, None], positions, seq_axis=1,
                                theta=spec.rope_theta)[:, :, 0]
            out, kl, chosen, choice = indexed_attention(
                q, k, v, qi, ki, wi,
                topk=spec.index_topk, tile=spec.index_tile)
            self.sow("intermediates", "indexer_kl", jnp.mean(kl))
            self.sow("intermediates", "keys_per_query",
                     jnp.mean(chosen.astype(jnp.float32)))
            # which keys each query saw: for a reader that asks (the
            # benchmark's reference follows the same choice); a step that
            # does not read it never builds it
            self.sow("intermediates", "key_choice", choice)
        else:
            if groups > 1:
                # broadcast KV heads to the full head count: the flash/ring
                # kernels stay head-symmetric (the GQA win here is params,
                # not compute)
                with jax.named_scope("attn_proj"):
                    k = jnp.repeat(k, groups, axis=2)
                    v = jnp.repeat(v, groups, axis=2)
            if self.seq_axis is not None:
                # [B, S, H, hd] -> [B, H, S, hd] for the ring's per-hop kernels
                qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
                out = ring_attention(qh, kh, vh, self.seq_axis, causal=True)
                out = out.transpose(0, 2, 1, 3)  # [B, S, H, hd]
            else:
                # dispatches on the sequence length: the Pallas flash kernels
                # from 512 tokens, which take [B, S, H, hd] as it is (one v5e
                # chip, forward + backward at the GPT-2 medium cells' shape:
                # 2.1 ms against 6.87 plain and 9.28 checkpointed XLA), fused
                # XLA attention below (ops/flash_attention.py has the table).
                out = flash_mha_seq_major(q, k, v, causal=True)
        with jax.named_scope("attn_proj"):
            return with_delta(
                "out",
                maybe_lora_dense(d, "out", rank=self.lora_rank,
                                 alpha=self.lora_alpha,
                                 targets=self.lora_targets, dtype=self.dtype,
                                 contract_ndim=2, use_bias=spec.bias)(out),
                out, cn=2)


    def _latent(self, x, positions):
        """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), the
        training form: ``cq = RMSNorm(x W_dq)``, ``[q_nope | q_rope] = cq
        W_uq`` a head; ``[ckv | k_rope] = x W_dkv``, ``ckv <- RMSNorm(ckv)``,
        ``k_nope = ckv W_uk``, ``v = ckv W_uv`` a head; the rotary parts
        turned (YaRN's frequencies where the spec says), ``k_rope`` ONE head
        shared by all query heads; softmax scale ``(nope + rope)^-1/2`` times
        YaRN's factor; q/k heads ``nope + rope`` wide and v heads
        ``v_head_dim`` go to the flash kernels as they are. No biases."""
        from ddw_tpu.ops.rope import apply_rope, yarn_softmax_factor

        spec = self.layer
        if positions is None:       # TransformerLM refuses decode and a ring
            raise ValueError("latent attention turns a part of every q and k "
                             "head: pos_encoding must be 'rope'")
        b, s, d = x.shape
        h, nope, rope = self.num_heads, spec.qk_nope_dim, spec.qk_rope_dim
        dense = lambda feats, name: nn.DenseGeneral(          # noqa: E731
            feats, use_bias=False, dtype=self.dtype, name=name)
        norm = lambda name: nn.RMSNorm(                       # noqa: E731
            epsilon=spec.norm_eps, dtype=jnp.float32, name=name)
        yarn = yarn_of(spec)
        turn = lambda t: apply_rope(t, positions, seq_axis=1,  # noqa: E731
                                    theta=spec.rope_theta, yarn=yarn)
        with jax.named_scope("attn_proj"):
            cq = norm("q_latent_norm")(dense(spec.q_lora_rank, "q_down")(x))
            q = dense((h, nope + rope), "q_up")(cq.astype(self.dtype))
            down = dense(spec.kv_lora_rank + rope, "kv_down")(x)
            ckv = norm("kv_latent_norm")(
                down[..., :spec.kv_lora_rank]).astype(self.dtype)
            k_rope = turn(down[..., None, spec.kv_lora_rank:])  # [B,S,1,rope]
            q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
            k = jnp.concatenate(
                [dense((h, nope), "k_up")(ckv),
                 jnp.broadcast_to(k_rope, (b, s, h, rope))], axis=-1)
            v = dense((h, spec.v_head_dim), "v_up")(ckv)
        scale = (nope + rope) ** -0.5 * (
            yarn_softmax_factor(spec.rope_factor) if yarn else 1.0)
        out = flash_mha_seq_major(q, k, v, causal=True, sm_scale=scale)
        with jax.named_scope("attn_proj"):
            return nn.DenseGeneral(d, axis=(-2, -1), use_bias=False,
                                   dtype=self.dtype, name="out")(out)


def routed_experts(spec: LayerSpec, num_held: int, mlp_dim: int, dtype,
                   name: str):
    """The spec's no-drop routed layer on the ``num_held`` experts this chip
    holds (``models/moe.py::RoutedExperts``), for either kind of block."""
    from ddw_tpu.models.moe import RoutedExperts

    return RoutedExperts(num_held, mlp_dim, k=spec.experts_per_token,
                         router_width=spec.router_width,
                         offset=spec.expert_offset, normalise=spec.norm_topk,
                         act=spec.mlp, dtype=dtype, score=spec.router_score,
                         scale=spec.router_scale,
                         bias_rate=spec.router_bias_rate,
                         shared_dim=spec.shared_expert_dim, name=name)


class DecoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    seq_axis: str | None = None
    decode: bool = False
    max_len: int = 2048
    slot_decode: bool = False
    num_experts: int = 0          # >0: MoE MLP (top-1/top-2) instead of dense
    expert_axis: str | None = None
    capacity_factor: float = 1.25
    moe_router: str = "top1"
    num_kv_heads: int = 0
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")
    paged_decode: bool = False
    kv_cache_blocks: int = 0
    kv_block_size: int = 0
    layer: LayerSpec = LayerSpec()

    @nn.compact
    def __call__(self, x, train: bool, positions=None, block_tables=None,
                 start_pos=None, adapters=None):
        spec = self.layer
        # x is then [B, S, n C], a token's streams side by side (the layout
        # the fused passes read: no copy between them and the blocks' edges);
        # TransformerLM refuses decode, a ring, LoRA
        streams = spec.hyper_streams > 1

        def joined(x, h):
            # with post_norm (the sandwich) h is a norm's float32 output:
            # the sum is made in float32 and rounded to the stream's dtype
            return (x + h).astype(x.dtype) if spec.post_norm else x + h

        if streams:
            flat = x.shape
            x = x.reshape(flat[:2] + (spec.hyper_streams, -1))
            h, h_post, h_res = HyperConnection(spec, name="hc_attn")(x)
            h = layer_norm(spec)(h)
        else:
            h = layer_norm(spec)(x)
        h = CausalSelfAttention(self.num_heads, self.dtype, self.seq_axis,
                                self.decode, self.max_len,
                                slot_decode=self.slot_decode,
                                num_kv_heads=self.num_kv_heads,
                                lora_rank=self.lora_rank,
                                lora_alpha=self.lora_alpha,
                                lora_targets=self.lora_targets,
                                paged_decode=self.paged_decode,
                                kv_cache_blocks=self.kv_cache_blocks,
                                kv_block_size=self.kv_block_size,
                                layer=spec,
                                name="attn")(h, positions=positions,
                                             block_tables=block_tables,
                                             start_pos=start_pos,
                                             adapters=adapters)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        if spec.post_norm:
            h = layer_norm(spec, "attn_post_norm")(h)
        if streams:
            x = hyper_write(x, h, h_post, h_res)
            h, h_post, h_res = HyperConnection(spec, name="hc_mlp")(x)
            h = layer_norm(spec)(h)
        else:
            x = joined(x, h)
            h = layer_norm(spec)(x)
        if spec.mlp not in ("gelu", "swiglu", "relu2") or (
                spec.mlp == "relu2" and not (self.num_experts
                                             and spec.experts_per_token)):
            raise ValueError(f"unknown mlp {spec.mlp!r}; use 'gelu' or "
                             f"'swiglu' ('relu2' for routed experts only)")
        if self.num_experts and spec.experts_per_token:
            h = routed_experts(spec, self.num_experts, self.mlp_dim,
                               self.dtype, "moe")(h)
        elif self.num_experts:
            from ddw_tpu.models.moe import MoEMlp

            h = MoEMlp(self.num_experts, self.mlp_dim,
                       capacity_factor=self.capacity_factor, dtype=self.dtype,
                       expert_axis=self.expert_axis, no_drop=self.decode,
                       router=self.moe_router, name="moe")(h)
        else:
            from ddw_tpu.models.lora import maybe_lora_dense, row_lora_delta

            d = h.shape[-1]

            def mlp_dense(feats, name, inp):
                y = maybe_lora_dense(feats, name, rank=self.lora_rank,
                                     alpha=self.lora_alpha,
                                     targets=self.lora_targets,
                                     dtype=self.dtype,
                                     use_bias=spec.bias)(inp)
                ab = (adapters or {}).get(name)
                if ab is not None:
                    y = y + row_lora_delta(inp, ab[0], ab[1]).astype(y.dtype)
                return y

            with jax.named_scope("mlp"):
                if spec.mlp == "swiglu":
                    h = mlp_dense(d, "down", nn.silu(
                        mlp_dense(self.mlp_dim, "gate", h))
                        * mlp_dense(self.mlp_dim, "up", h))
                else:
                    h = mlp_dense(self.mlp_dim, "fc1", h)
                    h = nn.gelu(h)
                    h = mlp_dense(d, "fc2", h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        if spec.post_norm:
            h = layer_norm(spec, "mlp_post_norm")(h)
        if streams:
            return hyper_write(x, h.astype(x.dtype), h_post,
                               h_res).reshape(flat)
        return joined(x, h)


MIXERS = {"M": "a Mamba-2 mixer", "E": "routed experts", "*": "attention"}


class MixerBlock(nn.Module):
    """One layer of a model with a ``pattern``: ONE mixer behind one norm,
    ``h + mixer(norm(h))``, the mixer's kind this layer's character of the
    pattern — ``M`` (:class:`ddw_tpu.models.mamba.Mamba2Mixer`), ``E``
    (:class:`ddw_tpu.models.moe.RoutedExperts` on the chip's share, with the
    spec's shared expert) or ``*`` (:class:`CausalSelfAttention`). Every kind
    reads its sizes from the one ``LayerSpec``. Training only."""

    kind: str
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    num_experts: int = 0
    num_kv_heads: int = 0
    layer: LayerSpec = LayerSpec()

    @nn.compact
    def __call__(self, x, train: bool, positions=None):
        spec = self.layer
        h = layer_norm(spec)(x)
        if self.kind == "M":
            from ddw_tpu.models.mamba import Mamba2Mixer

            h = Mamba2Mixer(spec, self.dtype, name="mixer")(h)
        elif self.kind == "E":
            h = routed_experts(spec, self.num_experts, self.mlp_dim,
                               self.dtype, "mixer")(h)
        else:
            h = CausalSelfAttention(self.num_heads, self.dtype,
                                    num_kv_heads=self.num_kv_heads,
                                    layer=spec, name="mixer")(
                                        h, positions=positions)
        return x + h.astype(x.dtype)


_EXIT_CHUNK = 2048      # tokens whose logits an exit holds at a time


def exit_distribution(gate_logits):
    """A token's distribution over the exits from its gate's logits
    ``[passes, ...]``, in float32: with ``lam_t = sigmoid(logit_t)``,
    ``p_1 = lam_1``, ``p_t = lam_t prod_{j<t} (1 - lam_j)``, and the LAST exit
    takes what is left, ``prod_{j<passes} (1 - lam_j)``, so that the
    ``passes`` numbers sum to 1 (the last pass's own gate is not read)."""
    lam = jax.nn.sigmoid(gate_logits.astype(jnp.float32))[:-1]
    left = jnp.cumprod(1.0 - lam, axis=0)       # after exits 1..t
    before = jnp.concatenate([jnp.ones_like(left[:1]), left[:-1]], axis=0)
    return jnp.concatenate([lam * before, left[-1:]], axis=0)


def run_passes(model, x, positions, targets, token_reading, block,
               make_head):
    """The looped stack: ``h_t = norm(blocks(h_{t-1}))`` for ``passes``
    passes over the SAME blocks and final norm, as one ``nn.scan`` whose
    parameters are broadcast, so that the compiled program holds a
    block's forward and backward once and the weights' gradient is the
    scan's sum over the passes. The carried state is the final norm's
    float32 output; a pass reads it in the model's dtype.

    Returns the last pass's logits; with an ``exit_gate`` and
    ``targets [B, S]`` the exits' readings in their place: a dict of
    ``[passes, B, S]`` float32, ``gate`` the gate's logit and whatever
    ``token_reading(logits, targets)`` (the step's: a dict of arrays a
    token) reads of an exit's logits, with every exit's logits made
    inside the loop and made again in the backward pass, never kept: what
    survives an exit's forward pass is its state and these ``[B, S]``."""
    read = targets is not None

    def chunk_reading(mdl, _, h_tg):
        h, tg = h_tg
        with jax.named_scope("head"):
            logits = make_head()(h)
        with jax.named_scope("loss"):
            return None, token_reading(logits, tg)

    def exit_reading(mdl, h, tg):
        # at most _EXIT_CHUNK tokens' logits at a time, each chunk's made
        # again in the backward pass: a whole row's over a large vocabulary,
        # with their gradient, are most of a chip. Equal chunks; a count
        # they do not divide is filled up to it (fewer tokens than there
        # are chunks, read and dropped)
        n = -(-tg.size // _EXIT_CHUNK)
        chunk = -(-tg.size // n)
        fill = n * chunk - tg.size
        h, flat = h.reshape(-1, h.shape[-1]), tg.reshape(-1)
        if fill:
            h = jnp.pad(h, ((0, fill), (0, 0)))
            flat = jnp.pad(flat, (0, fill))
        out = nn.scan(
            nn.remat(chunk_reading, prevent_cse=False),
            variable_broadcast="params", split_rngs={"params": False})(
                mdl, None, (h.reshape(n, chunk, -1),
                            flat.reshape(n, chunk)))[1]
        return jax.tree.map(
            lambda r: r.reshape(-1)[:tg.size].reshape(tg.shape), out)

    def pass_exit(mdl, x, tg):
        h = layer_norm(model.layer)(x)
        out = {}
        if model.exit_gate:
            with jax.named_scope("loss"), jax.named_scope("exit_gate"):
                out["gate"] = nn.Dense(1, dtype=jnp.float32,
                                       precision=lax.Precision.HIGHEST,
                                       name="exit_gate")(h)[..., 0]
        if read:
            out.update(exit_reading(mdl, h, tg))
        return h, out

    if model.remat != "none":
        # as a block: what a pass keeps of its exit is the stack's output
        # (in the model's dtype), not the norm's float32 working
        pass_exit = nn.remat(pass_exit, prevent_cse=False)

    def one_pass(mdl, h, positions, tg):
        x = h.astype(model.dtype)
        for i in range(model.depth):
            x = block(i, x, positions)
        return pass_exit(mdl, x, tg)

    h, out = nn.scan(
        one_pass, variable_broadcast="params",
        split_rngs={"params": False, "dropout": True},
        in_axes=nn.broadcast, length=model.passes)(
            model, x.astype(jnp.float32), positions, targets)
    if read:
        return out
    if model.exit_gate:
        model.sow("intermediates", "exit_gate_logits", out["gate"])
    with jax.named_scope("head"):
        return make_head()(h)


class TransformerLM(nn.Module):
    """Decoder-only LM over integer token ids.

    ``__call__(tokens[B, S]) -> logits[B, S, vocab]``. With ``seq_axis`` set the
    module must run inside ``shard_map`` with ``tokens`` sharded along the
    sequence dim; S is then the local shard length and positions are offset by
    the shard index. ``max_len`` bounds the *global* sequence length.
    """

    vocab_size: int = 256
    max_len: int = 2048
    hidden: int = 256
    depth: int = 4
    num_heads: int = 4
    mlp_dim: int = 1024
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    seq_axis: str | None = None
    decode: bool = False     # KV-cached autoregressive mode (see generate())
    slot_decode: bool = False  # continuous-batching decode: the batch dim is
                             # a serving slot pool, each row at its own depth
                             # (per-row cache/position indices; see
                             # ddw_tpu.serve.slots.SlotPool). Implies decode.
    paged_decode: bool = False  # paged continuous batching: K/V in a global
                             # fixed-size-block pool; per-row block tables
                             # and start positions are passed as ARGUMENTS
                             # (__call__(tokens, block_tables=, start_pos=))
                             # so the cache tree is batch-independent — the
                             # substrate of ddw_tpu.serve.blocks.BlockPool.
    kv_cache_blocks: int = 0  # paged: pool size (usable blocks + null)
    kv_block_size: int = 0   # paged: tokens per block (divides the tile)
    num_experts: int = 0     # >0: MoE MLP blocks (expert parallelism via
    expert_axis: str | None = None  # expert_axis inside shard_map)
    capacity_factor: float = 1.25
    moe_router: str = "top1"  # "top1" (Switch) or "top2" (GShard)
    num_kv_heads: int = 0    # GQA: KV heads (0 = num_heads); decode cache and
                             # k/v params shrink by num_heads/num_kv_heads
    lora_rank: int = 0       # >0: rank-r LoRA adapters (ddw_tpu.models.lora)
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")
    pos_encoding: str = "learned"  # "learned" absolute table (bounded by
                                   # max_len) or "rope" rotary relative
                                   # positions (ddw_tpu.ops.rope)
    remat: str = "none"      # activation rematerialization per block:
                             # "none" | "full" (nothing saved — recompute the
                             # block in backward) | "dots" (save matmul
                             # outputs, recompute elementwise). Ignored in
                             # decode mode (no backward there).
    layer: LayerSpec = LayerSpec()  # what every block is made of
    pattern: str = ""        # one mixer a layer (MixerBlock), a character
                             # each; "": every layer a DecoderBlock
    dense_layers: int = 0    # leading DecoderBlocks with the dense MLP at
    dense_mlp_dim: int = 0   # this width where the rest route
    mtp_depth: int = 0       # 0 | 1: the multi-token-prediction module
    passes: int = 1          # > 1: the blocks and the final norm run this
                             # many times over ONE set of weights
    exit_gate: bool = False  # an exit a pass through the one head, and a
                             # learned gate a token and pass (run_passes)

    @nn.compact
    def __call__(self, tokens, train: bool = False, block_tables=None,
                 start_pos=None, adapters=None, targets=None,
                 token_reading=None):
        # adapters: optional (stacks, idx) pair for heterogeneous-adapter
        # batched serving (ddw_tpu.serve.adapters.AdapterPool). ``stacks`` is
        # {f"backbone_block{i}": {target: (a_stack [S+1,*in,r],
        # b_stack [S+1,r,*feats])}} with slot 0 all-zeros (the null adapter);
        # ``idx`` is a per-row [B] int32 slot vector. The gather happens ONCE
        # here; each block then applies its row-wise delta. Passed as a call
        # ARGUMENT (like block_tables) so adapter churn never retraces.
        if self.lora_rank:
            from ddw_tpu.models.lora import validate_lora_targets

            validate_lora_targets(self.lora_targets)
        if self.pos_encoding not in ("learned", "rope", "none"):
            raise ValueError(f"unknown pos_encoding {self.pos_encoding!r}; "
                             f"use 'learned', 'rope' or 'none'")
        if self.pattern:
            if set(self.pattern) - set(MIXERS) or len(self.pattern) != self.depth:
                raise ValueError(
                    f"pattern {self.pattern!r} must give one of "
                    f"{sorted(MIXERS)} for each of the {self.depth} layers")
            if self.decode or self.seq_axis is not None or self.lora_rank:
                raise NotImplementedError(
                    "a model with a pattern trains on one device's whole "
                    "sequence: a cache of the state-space layers' state, a "
                    "ring over it and adapters are not written (ROADMAP M5)")
        if self.pos_encoding == "rope" and (
                self.layer.head_dim or self.hidden // self.num_heads) % 2:
            raise ValueError("RoPE needs an even head_dim")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {self.mtp_depth}: one module (1) "
                             f"or none (0)")
        streams = self.layer.hyper_streams if self.layer.hyper_streams > 1 else 0
        if (streams or self.mtp_depth
                or self.layer.attention == "latent") and (
                self.decode or self.seq_axis is not None or self.lora_rank
                or self.pattern):
            raise NotImplementedError(
                "latent attention, a hyper-connected residual path and the "
                "multi-token-prediction module train on one device's whole "
                "sequence: the latent cache (ROADMAP M4), drafting from the "
                "module (M7), a ring and adapters are not written")
        looped = self.passes > 1 or self.exit_gate
        if self.passes < 1 or (self.exit_gate and self.passes < 2):
            raise ValueError(f"passes {self.passes}: at least 1, and at "
                             f"least 2 for the exits an exit_gate weighs")
        if looped and (
                self.decode or self.seq_axis is not None or self.lora_rank
                or self.pattern or streams or self.mtp_depth
                or self.num_experts or self.layer.sows):
            raise NotImplementedError(
                "a stack run several times over one set of weights, and its "
                "exit gate, train on one device's whole sequence through "
                "plain blocks: a cache of keys and values a layer AND pass, "
                "a ring, adapters, a pattern, streams, the "
                "multi-token-prediction module and layers that sow are not "
                "written for it (ROADMAP M11)")
        if self.layer.post_norm and (self.decode or self.pattern):
            raise NotImplementedError(
                "a norm on each sublayer's output is written for the "
                "training path of a DecoderBlock: decode has not run it and "
                "a pattern's MixerBlock has no such norm (ROADMAP M2)")
        if targets is not None and not (self.exit_gate and token_reading):
            raise ValueError("targets are for a model with an exit_gate, "
                             "which reads its exits' logits itself with "
                             "the step's token_reading")
        b, s_local = tokens.shape
        embed = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                         name="tok_embed")

        def embedded(ids):
            e = embed(ids)
            return e * self.layer.embed_scale \
                if self.layer.embed_scale != 1.0 else e

        with jax.named_scope("embed"):      # the look-up; positions below
            x = embedded(tokens)
        if self.pos_encoding == "learned":
            pos_table = self.param("pos_embed", nn.initializers.normal(0.02),
                                   (self.max_len, self.hidden), jnp.float32)
        if self.decode and self.paged_decode:
            # paged mode: depth is per-request HOST state (the BlockPool's
            # stream records), handed in per call — no pos_index variable, so
            # the same cache tree serves a G-row prefill group and the
            # R-row decode batch without re-init.
            if start_pos is None:
                start_pos = jnp.zeros((b,), jnp.int32)
            offset = start_pos
        elif self.decode:
            # position = number of tokens already decoded (the attention layers
            # keep per-layer indices; this top-level one feeds the pos embed).
            # Past max_len the attention layers NaN-poison the output (loud
            # failure); generate() additionally raises host-side up front.
            # Slot mode keeps one position per pool row ([B] vector).
            pos_idx = self.variable(
                "cache", "pos_index",
                lambda: jnp.zeros((b,) if self.slot_decode else (),
                                  jnp.int32))
            offset = pos_idx.value
            pos_idx.value = offset + s_local
        elif self.seq_axis is not None:
            # Global length = s_local * axis_size must fit the position table:
            # dynamic_slice clamps out-of-range offsets, which would silently
            # reuse the last positions on trailing shards instead of failing.
            # (RoPE has no table — positions extrapolate, so SP sequences may
            # exceed max_len; only the decode cache stays bounded by it.)
            n_shards = axis_size(self.seq_axis)
            if (self.pos_encoding == "learned"
                    and s_local * n_shards > self.max_len):
                raise ValueError(
                    f"global sequence {s_local}*{n_shards} exceeds max_len "
                    f"{self.max_len}")
            offset = lax.axis_index(self.seq_axis) * s_local
        else:
            offset = 0
        if self.pos_encoding == "learned":
            if self.decode and (self.slot_decode or self.paged_decode):
                # per-row gather: row i reads the table at its own depth
                # (jnp.take clamps out-of-range rows — harmless, attention
                # NaN-poisons those rows anyway)
                rows = offset[:, None] + jnp.arange(s_local)  # [B, S]
                with jax.named_scope("embed"):
                    pos = jnp.take(pos_table, rows, axis=0)   # [B, S, hidden]
                    x = x + pos.astype(self.dtype)
            else:
                with jax.named_scope("embed"):
                    pos = lax.dynamic_slice_in_dim(pos_table, offset, s_local,
                                                   axis=0)
                    x = x + pos.astype(self.dtype)[None]
            positions = None
        elif self.pos_encoding == "none":
            positions = None
        else:
            # RoPE: absolute positions feed the per-layer q/k rotation; no
            # table, no additive embedding. Works unchanged under SP (offset
            # = shard_index * s_local, K rotated before the ring) and decode
            # (offset = tokens already written to the cache; [B]-shaped in
            # slot mode, giving [B, S] per-row positions).
            if self.decode and (self.slot_decode or self.paged_decode):
                positions = offset[:, None] + jnp.arange(s_local)
            else:
                positions = offset + jnp.arange(s_local)
        if self.remat not in ("none", "full", "dots"):
            raise ValueError(f"unknown remat {self.remat!r}; use 'none', "
                             f"'full' or 'dots'")
        if self.remat != "none" and not self.decode:
            # Rematerialized blocks: backward recomputes the block forward
            # instead of keeping its activations resident — O(depth) fewer
            # live activations for ~1/3 more FLOPs ('full' keeps nothing;
            # 'dots' keeps matmul outputs, recomputing only elementwise ops).
            # The decode path never differentiates, so it stays un-wrapped.
            # "full" keeps two things of a layer that chooses its keys
            # (ops/indexed_attention.py names them): the choice, one byte a
            # pair, and what the attention under it gave, so that neither is
            # made a second time; and of a layer that routes (models/moe.py)
            # the experts' first products, whose time follows the routing,
            # written in the chunks of the dispatch that ran; the sort their
            # rows lie in (order, slots and group sizes, int32), so that the
            # backward pass runs those chunks and lays its rows against the
            # kept product whatever a second making of the router would say;
            # and the choice of experts where the router names it (the
            # sigmoid router: models/moe.py::chosen), which the gates are
            # gathered by; nothing where no layer chooses or routes
            policy = (jax.checkpoint_policies.save_only_these_names(
                          "key_mask", "attention_out", "expert_choice",
                          "expert_sort", "expert_hidden")
                      if self.remat == "full"
                      else jax.checkpoint_policies.checkpoint_dots)
            # inside the passes' loop the barriers that keep a block's
            # second making apart from its first are not needed (the two lie
            # in different loops) and would only pin the loop's schedule
            remat = lambda block: nn.remat(                # noqa: E731
                block, static_argnums=(2,), policy=policy,
                prevent_cse=not looped)
        else:
            remat = lambda block: block                     # noqa: E731
        Block = remat(DecoderBlock)
        paged_kw = (dict(block_tables=block_tables, start_pos=start_pos)
                    if self.paged_decode else {})
        row_adapters = None
        if adapters is not None:
            stacks, aidx = adapters
            aidx = jnp.asarray(aidx, jnp.int32)
            row_adapters = jax.tree.map(lambda st: jnp.asarray(st)[aidx],
                                        stacks)
        def enter(x):       # entry: copied to every stream, side by side
            return x if not streams else jnp.tile(x, (1, 1, streams))

        def leave(x):       # exit: the streams summed
            if not streams:
                return x
            with jax.named_scope("hyper_conn"):
                return sum(jnp.split(x.astype(jnp.float32), streams,
                                     axis=2)).astype(x.dtype)

        x = enter(x)
        for i, kind in enumerate(self.pattern):
            x = remat(MixerBlock)(
                kind, self.num_heads, self.mlp_dim, self.dtype,
                num_experts=self.num_experts, num_kv_heads=self.num_kv_heads,
                layer=self.layer, name=f"backbone_block{i}")(x, train,
                                                             positions)

        def block(i, x, positions):
            blk_kw = dict(paged_kw)
            if row_adapters is not None:
                blk_kw["adapters"] = row_adapters.get(f"backbone_block{i}")
            dense = i < self.dense_layers
            return Block(self.num_heads,
                         self.dense_mlp_dim if dense else self.mlp_dim,
                         self.dropout,
                         self.dtype, None if self.decode else self.seq_axis,
                         self.decode, self.max_len,
                         slot_decode=self.slot_decode,
                         num_experts=0 if dense else self.num_experts,
                         expert_axis=None if self.decode else self.expert_axis,
                         capacity_factor=self.capacity_factor,
                         moe_router=self.moe_router,
                         num_kv_heads=self.num_kv_heads,
                         lora_rank=self.lora_rank,
                         lora_alpha=self.lora_alpha,
                         lora_targets=self.lora_targets,
                         paged_decode=self.paged_decode,
                         kv_cache_blocks=self.kv_cache_blocks,
                         kv_block_size=self.kv_block_size,
                         layer=self.layer,
                         name=f"backbone_block{i}")(x, train, positions,
                                                    **blk_kw)

        # vocab head in f32: logits feed a softmax CE, keep full precision
        make_head = lambda: nn.Dense(                       # noqa: E731
            self.vocab_size, use_bias=self.layer.bias, dtype=jnp.float32,
            name="head")
        if looped:
            return run_passes(self, x, positions, targets, token_reading,
                              block, make_head)
        # without a pattern: depth blocks of an attention and an MLP each
        for i in range(0 if self.pattern else self.depth):
            x = block(i, x, positions)
        x = leave(x)
        final_norm = layer_norm(self.layer)
        head = make_head()
        with jax.named_scope("head"):
            logits = head(final_norm(x))
        if self.mtp_depth:
            # DeepSeek-V3's module (arXiv:2412.19437, section 2.2), depth 1:
            # position i joins the trunk's output h_i with the embedding of
            # token i + 1 and predicts token i + 2 through one more block and
            # the SHARED embedding, final norm and head. The row's last
            # position has no token i + 1 (it reads the row's first: causal
            # attention lets no other position see it) and no target; the
            # step's loss leaves it out (train/lm_step.py).
            with jax.named_scope("mtp"):
                norm = lambda name: nn.RMSNorm(               # noqa: E731
                    epsilon=self.layer.norm_eps, dtype=jnp.float32, name=name)
                with jax.named_scope("embed"):
                    ahead = embedded(jnp.roll(tokens, -1, axis=1))
                joined = jnp.concatenate(
                    [norm("mtp_hidden_norm")(x), norm("mtp_embed_norm")(ahead)],
                    axis=-1).astype(self.dtype)
                with jax.named_scope("mtp_proj"):
                    h = nn.Dense(self.hidden, use_bias=False, dtype=self.dtype,
                                 name="mtp_proj")(joined)
                h = leave(Block(
                    self.num_heads, self.mlp_dim, self.dropout, self.dtype,
                    num_experts=self.num_experts,
                    num_kv_heads=self.num_kv_heads, layer=self.layer,
                    name="mtp_block")(enter(h), train, positions))
                with jax.named_scope("head"):
                    self.sow("intermediates", "mtp_logits",
                             head(final_norm(h)))
        return logits

    @staticmethod
    def frozen_prefixes(freeze_base: bool) -> tuple[str, ...]:
        return ()


def build_lm(cfg, seq_axis: str | None = None,
             expert_axis: str | None = None) -> TransformerLM:
    """Construct from an :class:`ddw_tpu.utils.config.LMCfg`."""
    return TransformerLM(
        vocab_size=cfg.vocab_size, max_len=cfg.max_len, hidden=cfg.hidden,
        depth=cfg.depth, num_heads=cfg.num_heads, mlp_dim=cfg.mlp_dim,
        dropout=cfg.dropout, dtype=jnp.dtype(cfg.dtype), seq_axis=seq_axis,
        num_experts=cfg.num_experts, expert_axis=expert_axis,
        capacity_factor=cfg.capacity_factor,
        moe_router=getattr(cfg, "moe_router", "top1"),
        num_kv_heads=getattr(cfg, "num_kv_heads", 0),
        lora_rank=getattr(cfg, "lora_rank", 0),
        lora_alpha=getattr(cfg, "lora_alpha", 16.0),
        lora_targets=tuple(getattr(cfg, "lora_targets", ("query", "value"))),
        pos_encoding=getattr(cfg, "pos_encoding", "learned"),
        remat=getattr(cfg, "remat", "none"),
        layer=getattr(cfg, "layer", LayerSpec()),
        pattern=getattr(cfg, "pattern", ""),
        dense_layers=getattr(cfg, "dense_layers", 0),
        dense_mlp_dim=getattr(cfg, "dense_mlp_dim", 0),
        mtp_depth=getattr(cfg, "mtp_depth", 0),
        passes=getattr(cfg, "passes", 1),
        exit_gate=getattr(cfg, "exit_gate", False))


def init_cache(decode_model: TransformerLM, batch: int):
    """Fresh zeroed KV cache for ``decode_model`` (constructed with
    ``decode=True``). Shapes come from ``eval_shape`` (no param allocation or
    forward run; ``init`` itself would *run* a decode step and leave the dummy
    token in the returned cache)."""
    shapes = jax.eval_shape(
        lambda: decode_model.init({"params": jax.random.PRNGKey(0)},
                                  jnp.zeros((batch, 1), jnp.int32)))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])


def set_cache_lengths(cache, length):
    """Rewrite every per-layer ``cache_index`` and the top-level ``pos_index``
    in a decode cache to ``length`` (broadcast to the leaf's shape). Used by
    padded-bucket prefill: the prompt is right-padded to a bucket, prefilled
    in one forward, then the indices snap back to the TRUE length so decode
    overwrites the pad garbage row by row (never attends it — positions past
    a query are causally masked, and the row at the write position is
    replaced before attention runs)."""
    def fix(path, leaf):
        name = getattr(path[-1], "key", None) if path else None
        if name in ("cache_index", "pos_index"):
            return jnp.full(leaf.shape, length, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def generate(model: TransformerLM, params, prompt, num_steps: int,
             rng: jax.Array | None = None, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 0.0, prompt_len=None):
    """Autoregressive continuation via the KV-cached decode path.

    ``prompt`` is int32 ``[B, P]``; returns ``[B, num_steps]`` continuation
    tokens. Greedy when ``temperature == 0``, else categorical sampling with
    ``rng``; ``top_k > 0`` restricts sampling to the k highest logits and
    ``top_p > 0`` to the smallest nucleus whose probability mass reaches p
    (both masks compose: k first, then p). Total length ``P + num_steps``
    must fit ``model.max_len``. Prefill is one batched causal forward (bulk
    K/V cache write); decode is a ``lax.scan`` with O(1) per-token cost
    against the static-shape cache — the whole thing jits to one XLA program.

    ``prompt_len`` (optional, may be a traced scalar): the TRUE shared prompt
    length when ``prompt`` is right-padded to a shape bucket — continuation
    starts after position ``prompt_len - 1`` and decode overwrites the pad
    region. This is what lets callers jit one program per bucket instead of
    one per prompt length (:class:`ddw_tpu.serving.LMPackagedModel`).
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    b, plen = prompt.shape
    if plen > model.max_len or (
            prompt_len is None and plen + num_steps > model.max_len):
        raise ValueError(f"prompt {plen} + steps {num_steps} exceeds "
                         f"max_len {model.max_len}")
    if temperature < 0.0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature != 0.0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if top_k < 0:
        raise ValueError(f"top_k must be >= 0, got {top_k}")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")
    if (top_k or top_p) and temperature == 0.0:
        raise ValueError("top_k/top_p require temperature > 0 (greedy decode "
                         "ignores them silently otherwise)")
    dm = model.clone(decode=True, seq_axis=None, dropout=0.0)
    cache = init_cache(dm, b)

    def run(cache, toks):
        logits, vars_ = dm.apply({"params": params, "cache": cache},
                                 toks, mutable=["cache"])
        return vars_["cache"], logits

    # Prefill: one batched causal forward writes the prompt's K/V in bulk.
    cache, prefill_logits = run(cache, prompt)
    if prompt_len is None:
        last_logits = prefill_logits[:, -1]
    else:
        # padded-bucket prefill: continue from the last REAL token and snap
        # the cache indices back so decode overwrites the pad region
        last_logits = jnp.take(prefill_logits,
                               jnp.asarray(prompt_len) - 1, axis=1)
        cache = set_cache_lengths(cache, jnp.asarray(prompt_len, jnp.int32))

    def pick(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits.astype(jnp.float32) / temperature
        if top_k:
            # keep the k highest logits per row; everything else -> -inf
            kth = lax.top_k(logits, min(top_k, logits.shape[-1]))[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p:
            # nucleus: smallest prefix of the sorted distribution with
            # cumulative probability >= top_p stays; rest -> -inf
            srt = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # number of kept entries: first index where cum >= p, inclusive
            keep = jnp.sum((cum - probs) < top_p, axis=-1, keepdims=True)
            cutoff = jnp.take_along_axis(srt, keep - 1, axis=-1)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    keys = (jax.random.split(rng, num_steps) if rng is not None
            else jnp.zeros((num_steps, 2), jnp.uint32))

    def step(carry, key):
        cache, logits = carry
        tok = pick(logits, key)
        cache, logits = run(cache, tok[:, None])
        return (cache, logits[:, 0]), tok

    (_, _), toks = lax.scan(step, (cache, last_logits), keys)
    return toks.T  # [B, num_steps]
