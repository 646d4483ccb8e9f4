"""Pipeline parallelism for the LM — GPipe microbatch schedule over a mesh axis.

Not a reference-parity item (the reference's parallelism inventory is
DP/trial/HPO/batch-inference, SURVEY.md §2d); this is the pipeline axis of the
framework, closing the tp/pp/dp/sp/ep set.

TPU-first formulation:

- the transformer's blocks are **stacked per stage**: block params become
  leaves ``[n_stages, blocks_per_stage, ...]`` sharded ``P('pipe')`` on the
  stage dim, so each device holds exactly its stage's weights (true model
  partitioning, not replication). Embed/head stay replicated (they are tiny).
- inside one ``shard_map``, a ``lax.scan`` runs the GPipe schedule: at tick
  ``t`` stage ``r`` processes microbatch ``t - r``; activations hop to the
  next stage over ICI via ``lax.ppermute``; ticks before/after a stage's
  window compute on masked garbage whose loss contribution is zeroed (SPMD
  ranks must run the same program — masking, not control flow, encodes the
  schedule).
- each stage applies its ``blocks_per_stage`` blocks with an inner
  ``lax.scan`` over the stacked block params, wrapped in ``jax.checkpoint``
  (per-tick rematerialization — GPipe's memory model).
- backward is plain ``jax.grad`` through the scan: XLA transposes the
  ``ppermute`` hops into the reverse-direction cotangent hops automatically.
  Stage grads stay stage-local (``P('pipe')`` out-spec); embed/head grads are
  ``psum``-ed (only the stages that actually use them contribute non-zeros).
- the optimizer update runs OUTSIDE the shard_map under ``jit``: stage
  params/moments arrive sharded, so GSPMD keeps the update sharded — the same
  split this framework uses for ZeRO (``parallel/zero.py``).

Scope: training/eval steps for :class:`ddw_tpu.models.lm.TransformerLM` with
``dropout == 0`` and ``seq_axis is None`` (PP composes with DP by adding a
data axis to the mesh; the batch dim shards over it transparently).

Two schedules (``make_pp_lm_train_step(schedule=...)``):

- ``"gpipe"`` — at tick ``t`` stage ``r`` processes microbatch ``t - r``;
  bubble fraction ``(n-1)/(m+n-1)``.
- ``"interleaved"`` — Megatron-style virtual stages: the depth splits into
  ``n * v`` chunks placed round-robin (chunk ``c`` on device ``c % n``), so
  every activation hop is still the same next-neighbor ``ppermute`` ring but
  each device re-enters the pipeline ``v`` times per microbatch. At tick
  ``t`` device ``r`` runs chunk ``k = (t-r) // n`` on microbatch
  ``j = (t-r) % n`` — a stall-free schedule exactly when ``m <= n`` (two
  chunks of one device would otherwise contend for the same tick; refused
  loudly). Ticks cost ``1/v`` of a GPipe tick, ``v*n + m - 1`` of them:
  bubble fraction ``(v*(n-m) + m-1 ... )`` — see :func:`bubble_fraction` —
  i.e. the GPipe bubble shrinks ~``v``-fold at equal microbatch count
  (n=4, m=4: 0.429 -> 0.273 at v=2). That matters in the real operating
  regime where ``m`` is pinned by per-microbatch memory, not free to grow.

Why no literal 1F1B: 1F1B's advantage over GPipe is peak activation memory
(O(n_stages) live microbatches instead of O(m)); its bubble fraction is the
same (n-1)/(m+n-1). Here every tick's stage application is
``jax.checkpoint``-ed, so the scan already retains only the [mb, S, H]
inter-stage activations per tick — 1F1B's memory profile — while backward
remains plain ``jax.grad`` (XLA transposes the schedule, ppermute hops
reverse automatically). A literal 1F1B would trade that for a hand-written
interleaved VJP schedule with no bubble improvement to show for it; the
interleaved virtual-stage schedule above is the variant that actually
reduces the bubble, and it keeps the plain-``jax.grad`` backward.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddw_tpu.models.lm import DecoderBlock, TransformerLM
from ddw_tpu.train.lm_step import lm_loss
from ddw_tpu.train.step import TrainState

PIPE_AXIS = "pipe"


def pp_params_from_lm(params: dict, n_stages: int, depth: int,
                      virtual_stages: int = 1) -> dict:
    """Restructure TransformerLM params for the pipeline step.

    ``virtual_stages == 1`` (GPipe): ``backbone_block{i}`` subtrees stack into
    ``stages`` leaves ``[n_stages, depth/n_stages, ...]`` — contiguous blocks
    per device. ``virtual_stages == v > 1`` (interleaved): the depth splits
    into ``n*v`` round-robin chunks (chunk ``c`` on device ``c % n``) and
    leaves stack ``[v, n_stages, depth/(n*v), ...]`` — ``leaf[k, r]`` is
    chunk ``k*n + r``. Everything else splits into the replicated ``embed``
    (token + position tables) and ``head`` (final LN + vocab projection)
    groups. Inverse: :func:`lm_params_from_pp`.
    """
    v = virtual_stages
    if depth % (n_stages * v):
        raise ValueError(f"depth {depth} not divisible by {n_stages} stages "
                         f"x {v} virtual stages")
    bpc = depth // (n_stages * v)
    blocks = [params[f"backbone_block{i}"] for i in range(depth)]

    def chunk_tree(c):
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *blocks[c * bpc:(c + 1) * bpc])

    if v == 1:
        stages = jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[chunk_tree(r) for r in range(n_stages)])
    else:
        rows = [jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[chunk_tree(k * n_stages + r)
                               for r in range(n_stages)])
                for k in range(v)]
        stages = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
    embed = {"tok_embed": params["tok_embed"]}
    if "pos_embed" in params:  # absent for pos_encoding='rope' models
        embed["pos_embed"] = params["pos_embed"]
    return {
        "embed": embed,
        "stages": stages,
        "head": {"LayerNorm_0": params["LayerNorm_0"],
                 "head": params["head"]},
    }


def lm_params_from_pp(pp: dict, n_stages: int, depth: int,
                      virtual_stages: int = 1) -> dict:
    """Inverse of :func:`pp_params_from_lm` (checkpoints/serving interop)."""
    v = virtual_stages
    bpc = depth // (n_stages * v)
    out = {"tok_embed": pp["embed"]["tok_embed"],
           "LayerNorm_0": pp["head"]["LayerNorm_0"],
           "head": pp["head"]["head"]}
    if "pos_embed" in pp["embed"]:  # absent for pos_encoding='rope' models
        out["pos_embed"] = pp["embed"]["pos_embed"]
    for c in range(n_stages * v):
        k, r = divmod(c, n_stages)
        for b in range(bpc):
            out[f"backbone_block{c * bpc + b}"] = jax.tree.map(
                (lambda x, r=r, b=b: x[r, b]) if v == 1
                else (lambda x, k=k, r=r, b=b: x[k, r, b]),
                pp["stages"])
    return out


def bubble_fraction(n_stages: int, num_microbatches: int,
                    virtual_stages: int = 1) -> float:
    """Idle fraction of the pipeline schedule (per device, fwd and bwd alike).

    GPipe (v=1): ``m`` busy of ``m + n - 1`` stage-ticks. Interleaved: ``m*v``
    busy of ``v*n + m - 1`` chunk-ticks (each 1/v the cost — the fraction is
    cost-invariant because all ticks are equal).
    """
    n, m, v = n_stages, num_microbatches, virtual_stages
    if v == 1:
        return (n - 1) / (m + n - 1)
    if m > n:
        raise ValueError(
            f"interleaved schedule is only defined for num_microbatches "
            f"({m}) <= n_stages ({n}) — the stall-free window "
            f"make_pp_lm_train_step enforces")
    return (v * n + m - 1 - v * m) / (v * n + m - 1)


def _spec_tree(pp_params, pipe_axis: str, virtual_stages: int = 1):
    """P('pipe') on the device-stage dim of stacked blocks (dim 0 for GPipe,
    dim 1 after the virtual-chunk dim for interleaved), replicated elsewhere."""
    stage_spec = P(pipe_axis) if virtual_stages == 1 else P(None, pipe_axis)
    return {
        "embed": jax.tree.map(lambda _: P(), pp_params["embed"]),
        "stages": jax.tree.map(lambda _: stage_spec, pp_params["stages"]),
        "head": jax.tree.map(lambda _: P(), pp_params["head"]),
    }


def make_pp_lm_train_step(
    model: TransformerLM,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    pipe_axis: str = PIPE_AXIS,
    data_axis: str | None = None,
    num_microbatches: int = 4,
    donate: bool = False,
    aux_loss_weight: float = 0.01,
    schedule: str = "gpipe",
    virtual_stages: int = 2,
) -> Callable:
    """Build the pipelined LM train step.

    ``step(state, inputs, targets) -> (state, metrics)`` where ``state.params``
    is the :func:`pp_params_from_lm` layout placed via ``step.place_state``.
    ``num_microbatches`` must divide the per-data-shard batch (checked at call
    time). With ``data_axis`` set (DPxPP mesh) the batch dim additionally
    shards over it: each data-parallel pipeline replica runs the schedule on
    its shard and gradients ``pmean`` across replicas. MoE models are
    supported with all-local (dense) experts — their Switch aux loss is
    accumulated across stages/microbatches like the non-PP step's; an
    ``expert_axis`` is rejected (PPxEP routing across a second axis is not
    implemented).

    ``schedule='gpipe'`` runs contiguous stages; ``schedule='interleaved'``
    places ``virtual_stages`` round-robin chunks per device (module
    docstring), cutting the bubble ~``virtual_stages``-fold at equal
    microbatch count; it requires ``num_microbatches <= n_stages`` (the
    stall-free window) and ``depth % (n_stages * virtual_stages) == 0``.
    Every step's metrics carry the schedule's analytic
    ``pp_bubble_fraction`` (:func:`bubble_fraction`).
    """
    if model.dropout:
        raise ValueError("pipeline step supports dropout=0 models only")
    if model.seq_axis:
        raise ValueError("pipeline step composes with DP, not SP — build the "
                         "model with seq_axis=None")
    if getattr(model, "expert_axis", None):
        raise ValueError("pipeline step does not implement expert parallelism "
                         "— build the MoE model with expert_axis=None (dense "
                         "experts) or use make_lm_train_step for EP")
    if getattr(model, "lora_rank", 0):
        raise ValueError("pipeline step does not support LoRA adapters — use "
                         "make_lm_train_step")
    rope = getattr(model, "pos_encoding", "learned") == "rope"
    n = mesh.shape[pipe_axis]
    m = num_microbatches
    if schedule not in ("gpipe", "interleaved"):
        raise ValueError(f"schedule must be 'gpipe' or 'interleaved', "
                         f"got {schedule!r}")
    v = virtual_stages if schedule == "interleaved" else 1
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")
    if model.depth % (n * v):
        raise ValueError(f"depth {model.depth} not divisible by pipe axis {n}"
                         + (f" x virtual_stages {v}" if v > 1 else ""))
    if schedule == "interleaved" and m > n:
        raise ValueError(
            f"interleaved schedule requires num_microbatches ({m}) <= "
            f"n_stages ({n}): beyond that window two chunks of one device "
            f"contend for the same tick (the stall-free property breaks) — "
            f"use schedule='gpipe' for large microbatch counts")
    moe = getattr(model, "num_experts", 0) > 0
    aux_w = aux_loss_weight
    bubble = bubble_fraction(n, m, v)

    block_mod = DecoderBlock(model.num_heads, model.mlp_dim, 0.0, model.dtype,
                             None, False, model.max_len,
                             num_experts=model.num_experts,
                             capacity_factor=model.capacity_factor,
                             moe_router=model.moe_router,
                             num_kv_heads=getattr(model, "num_kv_heads", 0))
    embed_mod = nn.Embed(model.vocab_size, model.hidden, dtype=model.dtype)
    ln_mod = nn.LayerNorm(dtype=jnp.float32)
    head_mod = nn.Dense(model.vocab_size, dtype=jnp.float32)

    @jax.checkpoint
    def stage_apply(stage_params, x):
        """Apply this stage's stacked blocks (inner scan over the block dim).
        Returns (out, aux_sum) — the stage's summed Switch aux loss (0 for
        dense models)."""
        def body(h, block_params):
            # RoPE: positions are global arange(S) — PP shards depth, not
            # sequence, so every stage sees the full sequence
            positions = jnp.arange(h.shape[-2]) if rope else None
            if moe:
                from ddw_tpu.models.moe import collect_sown

                out, mods = block_mod.apply({"params": block_params}, h, False,
                                            positions=positions,
                                            mutable=["intermediates"])
                # select the aux loss by name: blocks also sow routing
                # telemetry that must not enter the loss
                sown = collect_sown(mods, "moe_aux_loss")
                return out, sum(sown)
            return block_mod.apply({"params": block_params}, h, False,
                                   positions=positions), 0.0

        out, aux = lax.scan(body, x, stage_params)
        return out, jnp.sum(aux)

    def _forward(pp_params, inputs, targets):
        """Per-rank pipeline forward: the schedule scan, shared by the train
        step (under ``value_and_grad``) and the eval step (called plain).
        Returns ``(total_loss, (ce, acc, aux))``."""
        r = lax.axis_index(pipe_axis)
        b, s = inputs.shape
        if b % m:
            raise ValueError(f"per-shard batch {b} not divisible by "
                             f"num_microbatches {m}")
        mb = b // m
        perm = [(i, (i + 1) % n) for i in range(n)]

        def loss_fn(p):
            emb = embed_mod.apply({"params": p["embed"]["tok_embed"]}, inputs)
            if not rope:
                pos = p["embed"]["pos_embed"][:s].astype(model.dtype)[None]
                emb = emb + pos
            emb = emb.reshape(m, mb, s, model.hidden)
            targ = targets.reshape(m, mb, s)
            if v == 1:
                stage_params = jax.tree.map(lambda x: x[0], p["stages"])
            else:
                # local stages leaves are [v, 1, bpc, ...]: v round-robin
                # chunks resident on this device.
                local_chunks = jax.tree.map(lambda x: x[:, 0], p["stages"])

            def tick(carry, t):
                recv, ce_sum, acc_sum, aux_sum = carry
                if v == 1:
                    j = t - r
                    valid = (j >= 0) & (j < m)
                    first_chunk, last_chunk = r == 0, r == n - 1
                    sp = stage_params
                else:
                    # interleaved: device r runs chunk k = (t-r)//n on
                    # microbatch j = (t-r) % n — stall-free for m <= n.
                    q = t - r
                    k = jnp.clip(q // n, 0, v - 1)
                    j = q % n
                    valid = (q >= 0) & (q // n < v) & (j < m)
                    first_chunk = (r == 0) & (k == 0)
                    last_chunk = (r == n - 1) & (k == v - 1)
                    sp = jax.tree.map(
                        lambda x: lax.dynamic_index_in_dim(
                            x, k, keepdims=False), local_chunks)
                j_c = jnp.clip(j, 0, m - 1)
                x0 = lax.dynamic_index_in_dim(emb, j_c, keepdims=False)
                x_in = jnp.where(first_chunk, x0.astype(model.dtype),
                                 recv.astype(model.dtype))
                y, aux = stage_apply(sp, x_in)
                tgt = lax.dynamic_index_in_dim(targ, j_c, keepdims=False)

                # Head + CE only materialize on the last chunk: the head
                # projection has no collectives, so lax.cond is legal inside
                # shard_map and skips (n-1)/n of the vocab-matmul work.
                def head_ce(y):
                    logits = head_mod.apply(
                        {"params": p["head"]["head"]},
                        ln_mod.apply({"params": p["head"]["LayerNorm_0"]},
                                     y.astype(jnp.float32)))
                    ce = lm_loss(logits, tgt)
                    acc = jnp.mean(
                        (jnp.argmax(logits, -1) == tgt).astype(jnp.float32))
                    return ce, acc

                ce, acc = lax.cond(last_chunk, head_ce,
                                   lambda _: (jnp.zeros(()), jnp.zeros(())), y)
                use = (valid & last_chunk).astype(jnp.float32)
                # every chunk contributes its own aux for its valid ticks
                aux_use = valid.astype(jnp.float32)
                recv_next = lax.ppermute(y, pipe_axis, perm)
                return (recv_next, ce_sum + use * ce, acc_sum + use * acc,
                        aux_sum + aux_use * aux), None

            z = jnp.zeros((mb, s, model.hidden), model.dtype)
            n_ticks = (m + n - 1) if v == 1 else (v * n + m - 1)
            (_, ce_sum, acc_sum, aux_sum), _ = lax.scan(
                tick, (z, jnp.zeros(()), jnp.zeros(()), jnp.zeros(())),
                jnp.arange(n_ticks))
            # only the last stage accumulated CE; psum broadcasts the global
            # mean. Aux: every stage's blocks contributed once per microbatch
            # — mean over (microbatches x blocks) matches make_lm_train_step.
            loss = lax.psum(ce_sum, pipe_axis) / m
            acc = lax.psum(acc_sum, pipe_axis) / m
            aux = lax.psum(aux_sum, pipe_axis) / (m * model.depth)
            return loss + aux_w * aux, (loss, acc, aux)

        return loss_fn(pp_params)

    def grad_fn(pp_params, inputs, targets):
        """Per-rank pipeline forward+backward. inputs/targets [B, S] replicated
        over the pipe axis (shard them over a data axis for DPxPP)."""
        (_, (loss, acc, aux)), grads = jax.value_and_grad(
            lambda p: _forward(p, inputs, targets), has_aux=True)(pp_params)
        # The loss comes out of a psum, replicated on every rank; under
        # shard_map AD each rank's unit cotangent flows through the psum
        # transpose, so raw grads are n_stages x the true gradient (verified
        # empirically: every leaf exactly n x). Scale back.
        grads = jax.tree.map(lambda g: g / n, grads)
        # embed/head params are replicated but only some stages produce
        # non-zero grads — psum makes every rank's grad the true global one.
        grads["embed"] = lax.psum(grads["embed"], pipe_axis)
        grads["head"] = lax.psum(grads["head"], pipe_axis)
        metrics = _metrics(loss, acc, aux)
        if data_axis is not None:
            # DPxPP: average gradients across pipeline replicas (metrics
            # already pmean-ed in _metrics).
            grads = lax.pmean(grads, data_axis)
        return grads, metrics

    def _metrics(loss, acc, aux):
        """ONE metrics assembly for the train and eval halves — a metric
        added to one cannot silently miss the other."""
        metrics = {"loss": loss, "accuracy": acc}
        if moe:
            metrics["aux_loss"] = aux
        if data_axis is not None:
            metrics = lax.pmean(metrics, data_axis)
        return metrics

    def metrics_fn(pp_params, inputs, targets):
        """Forward-only pipeline metrics (the eval half of the step)."""
        _, (loss, acc, aux) = _forward(pp_params, inputs, targets)
        return _metrics(loss, acc, aux)

    def _build(template_params):
        specs = _spec_tree(template_params, pipe_axis, v)
        tok_spec = P() if data_axis is None else P(data_axis)
        smapped = shard_map(
            grad_fn, mesh=mesh,
            in_specs=(specs, tok_spec, tok_spec),
            out_specs=(specs, P()),
            check_vma=False)
        smapped_eval = shard_map(
            metrics_fn, mesh=mesh,
            in_specs=(specs, tok_spec, tok_spec),
            out_specs=P(),
            check_vma=False)

        def _step(state: TrainState, inputs, targets):
            grads, metrics = smapped(state.params, inputs, targets)
            # Analytic idle fraction of this schedule — surfaced per step so
            # trainers/trackers log the bubble beside throughput.
            metrics["pp_bubble_fraction"] = jnp.float32(bubble)
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            return TrainState(new_params, {}, new_opt, state.step + 1), metrics

        def _eval(state: TrainState, inputs, targets):
            return smapped_eval(state.params, inputs, targets)

        return (jax.jit(_step, donate_argnums=(0,) if donate else ()),
                jax.jit(_eval))

    bpc = model.depth // (n * v)

    def _check_layout(params):
        # A state built with the wrong virtual_stages fails far from the
        # mistake (opaque sharding/rank errors) — refuse here instead.
        leaf = jax.tree.leaves(params["stages"])[0]
        want = (n, bpc) if v == 1 else (v, n, bpc)
        if tuple(leaf.shape[:len(want)]) != want:
            raise ValueError(
                f"stages layout mismatch: leaf leading dims "
                f"{tuple(leaf.shape[:len(want)])} != {want} expected by "
                f"schedule={schedule!r} (virtual_stages={v}) — build the "
                f"state with init_pp_state(..., virtual_stages={v}) / "
                f"pp_params_from_lm(..., virtual_stages={v})")

    _jits: dict = {}

    def _fns(state: TrainState):
        key = jax.tree.structure(state)
        fns = _jits.get(key)
        if fns is None:
            _check_layout(state.params)
            fns = _jits[key] = _build(state.params)
        return fns

    def stepper(state: TrainState, inputs, targets):
        return _fns(state)[0](state, inputs, targets)

    def eval_step(state: TrainState, inputs, targets):
        """Forward-only metrics over the same schedule (no update, no
        donation — the state is reused across the whole eval pass)."""
        return _fns(state)[1](state, inputs, targets)

    stepper.eval_step = eval_step  # type: ignore[attr-defined]
    # the train half's executables, as a jitted function counts its own
    stepper._cache_size = lambda: sum(  # type: ignore[attr-defined]
        fns[0]._cache_size() for fns in _jits.values())

    def place_state(state: TrainState) -> TrainState:
        _check_layout(state.params)
        specs = _spec_tree(state.params, pipe_axis, v)
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        repl = NamedSharding(mesh, P())

        def opt_sharding(leaf):
            # Optimizer moments mirror the params tree; stacked stage leaves
            # are exactly the ones whose leading dims match the stacked-chunk
            # layout — shard those with the stages, replicate everything else
            # (including adam's count scalar).
            shape = getattr(leaf, "shape", ())
            if v == 1:
                if len(shape) >= 2 and tuple(shape[:2]) == (n, bpc):
                    return NamedSharding(mesh, P(pipe_axis))
            elif len(shape) >= 3 and tuple(shape[:3]) == (v, n, bpc):
                return NamedSharding(mesh, P(None, pipe_axis))
            return repl

        return TrainState(
            params=jax.tree.map(jax.device_put, state.params, psh),
            batch_stats={},
            opt_state=jax.tree.map(
                lambda leaf: jax.device_put(leaf, opt_sharding(leaf)),
                state.opt_state),
            step=jax.device_put(state.step, repl),
        )

    stepper.place_state = place_state  # type: ignore[attr-defined]
    return stepper


def init_pp_state(model: TransformerLM, tx: optax.GradientTransformation,
                  mesh: Mesh, rng: jax.Array,
                  pipe_axis: str = PIPE_AXIS,
                  virtual_stages: int = 1) -> TrainState:
    """Init a TransformerLM and restructure into placed pipeline TrainState.
    ``virtual_stages`` must match the step's (1 for ``schedule='gpipe'``)."""
    from ddw_tpu.train.lm_step import init_lm_state

    base = init_lm_state(model, tx, rng)
    n = mesh.shape[pipe_axis]
    pp = pp_params_from_lm(base.params, n, model.depth, virtual_stages)
    state = TrainState(pp, {}, tx.init(pp), jnp.zeros((), jnp.int32))
    return state
