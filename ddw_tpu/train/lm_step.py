"""Jitted LM train/eval steps over a (data, seq) mesh — DP x sequence parallelism.

The long-context analog of :mod:`ddw_tpu.train.step`: one ``shard_map``-ped XLA
program computes forward, backward, gradient reduction, and the optimizer update.
Tokens shard over *both* mesh axes — batch over ``data``, sequence over ``seq`` —
so a sequence N_seq times longer than one device's memory allows still trains;
attention runs as a ``ppermute`` ring (:mod:`ddw_tpu.parallel.ring_attention`)
whose hops ride ICI neighbor links.

Loss plumbing: callers pre-shift on the host (``inputs = tokens[:, :-1]``,
``targets = tokens[:, 1:]``) so no cross-shard halo exchange is needed at shard
boundaries; per-device mean CE is exact globally because every shard holds the
same token count (identical-shape guarantee, SURVEY.md §7 hard-part 2). Gradients
are averaged over data x seq by :func:`ddw_tpu.parallel.collectives.grad_mean`.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddw_tpu.parallel.collectives import (data_parallel_compile_options,
                                          grad_mean)
from ddw_tpu.train.step import (TrainState, cross_entropy_loss,
                                replicated_placer, token_cross_entropy)

# next-token CE is the same sparse CE (it broadcasts over [B, S, V] vs [B, S])
lm_loss = cross_entropy_loss


def _maybe_lora_tx(model, tx: optax.GradientTransformation):
    """A model built with ``lora_rank > 0`` gets the LoRA freezing mask
    applied HERE, in the shared optimizer layer — the same altitude where the
    CNN families' ``frozen_prefixes`` masking lives — so callers pass a plain
    optax transform and cannot accidentally full-fine-tune the frozen base
    alongside its adapters. Applied identically by :func:`init_lm_state` and
    :func:`make_lm_train_step` (the two places the transform is consumed)."""
    if getattr(model, "lora_rank", 0):
        from ddw_tpu.models.lora import lora_optimizer

        return lora_optimizer(tx)
    return tx


def init_lm_state(model, tx: optax.GradientTransformation,
                  rng: jax.Array, seq_len: int = 8) -> TrainState:
    """Seeded replicated init (identical on every host == rank-0 broadcast)."""
    tx = _maybe_lora_tx(model, tx)
    dummy = jnp.zeros((1, seq_len), jnp.int32)
    # An axis-bound (seq/expert-parallel) model must init outside shard_map:
    # build an axis-free twin — parameter shapes are axis-independent by
    # construction (stacked expert weights, global-position embeds).
    if model.seq_axis or getattr(model, "expert_axis", None):
        unbind = {"seq_axis": None}
        if hasattr(model, "expert_axis"):
            unbind["expert_axis"] = None
        init_model = model.clone(**unbind)
    else:
        init_model = model
    variables = init_model.init({"params": rng}, dummy, train=False)
    params = variables["params"]
    # leaves without a gradient, moved by their layer's rule after each step
    # (the routers' correction biases), ride where the vision models' batch
    # statistics do: outside ``params``, so the optimizer never sees them
    return TrainState(params, dict(variables.get("buffers", {})),
                      tx.init(params), jnp.zeros((), jnp.int32))


def layer_terms(mods: dict) -> dict:
    """The loss terms and counters the layers sowed, by name, each a mean over
    the layers that sowed it. ``moe_aux_loss`` (the capacity dispatch's balance
    term) and ``indexer_kl`` (ops/indexed_attention.py) are the two that
    carry a gradient."""
    from ddw_tpu.models.moe import collect_sown

    mean = lambda xs: sum(xs) / len(xs)                   # noqa: E731
    out = {name: mean(sown) for name in ("moe_aux_loss", "indexer_kl",
                                         "keys_per_query")
           if (sown := collect_sown(mods, name))}
    counts = collect_sown(mods, "moe_counts")
    if counts:
        out.update({"moe_" + name: mean([c[name] for c in counts])
                    for name in counts[0]})
        # the fullest routed block's, beside the mean over the blocks: a
        # block's grouped products run a further chunk by ITS held load
        out["moe_block_assignments_max"] = jnp.max(jnp.stack(
            [c["assignments_per_token"] for c in counts]))
    # counters a layer names itself: a mean over the layers that sowed each
    sown: dict = {}
    for layer in collect_sown(mods, "counters"):
        for name, value in layer.items():
            sown.setdefault(name, []).append(value)
    out.update({name: mean(values) for name, values in sown.items()})
    return out


def exit_token_reading(logits, targets) -> dict:
    """What the step reads of an exit's logits, a token (the model's
    ``token_reading``, called on a chunk of tokens inside its passes' loop):
    the cross-entropy and whether the first choice is the target."""
    return {"ce": token_cross_entropy(logits, targets),
            "correct": (jnp.argmax(logits, -1) == targets).astype(
                jnp.float32)}


def exit_loss(readings: dict, entropy_weight: float) -> tuple:
    """What a model with an exit gate descends, from its exits' readings
    (``models/lm.py::run_passes``: ``gate`` and :func:`exit_token_reading`'s
    ``ce`` and ``correct``, each ``[passes, B, S]``): the mean over tokens of ``sum_t p_t CE_t -
    entropy_weight H(p)``, ``p`` the token's distribution over the exits
    (:func:`ddw_tpu.models.lm.exit_distribution`) and ``H`` its entropy, all
    in float32. Returns ``(total, last exit's mean CE, last exit's accuracy,
    counters)``: ``exit_loss_t`` and ``exit_share_t`` (the means of ``CE_t``
    and ``p_t``), ``exit_expected_passes`` (``mean sum_t t p_t``: what
    leaving at the gate's word would run), ``exit_entropy`` (``mean H``, at
    most ``ln passes``) and ``exit_expected_loss`` (``mean sum_t p_t CE_t``:
    the total is this less ``entropy_weight`` times the entropy)."""
    from ddw_tpu.models.lm import exit_distribution

    ce = readings["ce"].astype(jnp.float32)
    with jax.named_scope("exit_gate"):
        p = exit_distribution(readings["gate"])
        # a saturated gate makes a p of exactly 0: 0 ln 0 = 0, and a finite
        # slope there
        entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
        expected = jnp.sum(p * ce, axis=0)
        total = jnp.mean(expected - entropy_weight * entropy)
        ranks = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
        terms = {"exit_expected_passes": jnp.mean(
                     jnp.tensordot(ranks, p, axes=1)),
                 "exit_entropy": jnp.mean(entropy),
                 "exit_expected_loss": jnp.mean(expected)}
        for t in range(p.shape[0]):
            terms[f"exit_loss_{t + 1}"] = jnp.mean(ce[t])
            terms[f"exit_share_{t + 1}"] = jnp.mean(p[t])
    return (total, jnp.mean(ce[-1]), jnp.mean(readings["correct"][-1]),
            terms)


def _lm_axes(model, data_axis: str, seq_axis: str | None) -> tuple:
    """Validate the model/step axis contract shared by the per-step and
    chained factories; returns ``(axes, sows)``: whether the model's layers
    sow loss terms or counters the step has to collect."""
    axes = (data_axis,) if seq_axis is None else (data_axis, seq_axis)
    if (model.seq_axis or None) != (seq_axis or None):
        raise ValueError(f"model.seq_axis={model.seq_axis!r} but step "
                         f"seq_axis={seq_axis!r} — construct the model with the "
                         f"axis it will run under")
    sows = (getattr(model, "num_experts", 0) > 0
            or getattr(model, "mtp_depth", 0) > 0
            or getattr(model, "layer", None) is not None and model.layer.sows)
    expert_axis = getattr(model, "expert_axis", None)
    if expert_axis and expert_axis not in axes:
        raise ValueError(f"model.expert_axis={expert_axis!r} is not a step "
                         f"mesh axis {axes}")
    return axes, sows


def make_lm_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: str | None = "seq",
    donate: bool = True,
    aux_loss_weight: float = 0.01,
    grad_accum_steps: int = 1,
    hand_out: tuple[str, ...] = (),
    mtp_weight: float = 0.1,
    exit_entropy_weight: float = 0.05,
) -> Callable:
    """Build the jitted DP(xSP)(xEP) LM train step.

    ``step(state, inputs, targets, rng) -> (state, metrics)`` with inputs/targets
    ``[global_batch, global_seq]`` sharded ``P(data_axis, seq_axis)``. The model's
    ``seq_axis`` must match ``seq_axis`` (or both be None for pure DP); a routing
    model's ``expert_axis`` must be one of the step's mesh axes (its all_to_alls
    then ride that axis). Metrics (loss, token accuracy) come back
    world-averaged; for MoE models the Switch load-balance aux loss is added
    with ``aux_loss_weight`` and reported as ``metrics['aux_loss']``; a model
    with a multi-token-prediction module (``mtp_depth``) descends ``loss +
    mtp_weight * mtp_loss``, the module's cross-entropy against the token
    after next over the positions that have one, and reports it among the
    layers' counters (``loss`` and ``accuracy`` stay the main head's); a
    model with an ``exit_gate`` descends :func:`exit_loss` with
    ``exit_entropy_weight`` and reports its counters there too (``loss`` and
    ``accuracy`` are the LAST exit's). It
    compiles once for each placement of its arguments:
    ``step.place_state(state)`` before the first call gives the state the
    placement the step returns it in, and one executable serves.

    ``hand_out`` names what the layers sow for a reader that asks (a routed
    layer's ``expert_choice``): the step then returns, as
    ``metrics["handed"][name]``, what the layers sowed under it ON THIS STEP,
    stacked over the layers in their order and gathered over the mesh — the
    discrete choices the update was computed with, which no second forward
    pass reproduces to the last token (the benchmark's reference follows
    them). Nobody else asks: a step without it builds none of it.
    """
    tx = _maybe_lora_tx(model, tx)
    axes, sows = _lm_axes(model, data_axis, seq_axis)
    options = data_parallel_compile_options(mesh, axes, model)
    _step = _make_lm_step_body(model, tx, axes, sows, options is not None,
                               aux_loss_weight, grad_accum_steps, hand_out,
                               mtp_weight, exit_entropy_weight)

    tok_spec = P(data_axis) if seq_axis is None else P(data_axis, seq_axis)
    smapped = shard_map(
        _step, mesh=mesh,
        in_specs=(P(), tok_spec, tok_spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    step = jax.jit(smapped, donate_argnums=(0,) if donate else (),
                   compiler_options=options)
    step.batch_sharding = NamedSharding(mesh, tok_spec)  # type: ignore[attr-defined]
    step.place_state = replicated_placer(mesh, donate)  # type: ignore[attr-defined]
    return step


def _make_lm_step_body(model, tx: optax.GradientTransformation, axes, sows,
                       fused: bool, aux_loss_weight: float,
                       grad_accum_steps: int,
                       hand_out: tuple[str, ...] = (),
                       mtp_weight: float = 0.1,
                       exit_entropy_weight: float = 0.05):
    """The per-update shard_map body shared by :func:`make_lm_train_step`
    and :func:`make_lm_train_chain` (which scans it K times). ``fused``: the
    builder's ``jit`` got :func:`data_parallel_compile_options`, so the
    means take :func:`grad_mean`'s form; else they are the ``lax.pmean`` calls
    they were, in their order."""
    from flax.traverse_util import flatten_dict

    from ddw_tpu.models.moe import collect_sown

    exits = getattr(model, "exit_gate", False)
    if hand_out and (not sows or grad_accum_steps > 1):
        raise ValueError("hand_out returns what the layers sow on a step: "
                         "the model's layers sow nothing, or "
                         "grad_accum_steps splits the step into several")

    def _step(state: TrainState, inputs, targets, rng):
        # independent dropout masks per (data shard, seq shard, step)
        for ax in axes:
            rng = jax.random.fold_in(rng, lax.axis_index(ax))
        dropout_rng = jax.random.fold_in(rng, state.step)

        buffers = state.batch_stats

        def loss_fn(params, in_mb, tg_mb, rng_mb):
            terms, loads, handed = {}, {}, {}
            if exits:
                # the model reads its exits against the targets itself: no
                # exit's logits outlive the pass that makes them
                readings = model.apply({"params": params}, in_mb, train=True,
                                       rngs={"dropout": rng_mb},
                                       targets=tg_mb,
                                       token_reading=exit_token_reading)
                with jax.named_scope("loss"):
                    total, ce, acc, terms = exit_loss(readings,
                                                      exit_entropy_weight)
                return total, (ce, acc, jnp.zeros((), jnp.float32), terms,
                               loads, handed)
            if sows:
                variables = {"params": params}
                if buffers:
                    variables["buffers"] = buffers
                logits, mods = model.apply(
                    variables, in_mb, train=True,
                    rngs={"dropout": rng_mb}, mutable=["intermediates"])
                # one sown value a layer, a mean over the layers. Selected by
                # name — blocks also sow routing telemetry (drop rate,
                # balance entropy, gate logits) that must not leak in.
                terms = layer_terms(mods)
                # every routed layer's load by expert, which its correction
                # bias moves by after the update
                loads = {path: sown for path, sown in flatten_dict(
                    mods["intermediates"]).items()
                    if path[-1] == "router_load"}
                handed = {name: jnp.stack(collect_sown(mods, name))
                          for name in hand_out}
            else:
                logits = model.apply({"params": params}, in_mb, train=True,
                                     rngs={"dropout": rng_mb})
            aux = terms.pop("moe_aux_loss", jnp.zeros((), jnp.float32))
            with jax.named_scope("loss"):
                ce = lm_loss(logits, tg_mb)
            acc = jnp.mean((jnp.argmax(logits, -1) == tg_mb).astype(jnp.float32))
            # ``loss`` stays the cross-entropy; the indexer's KL term reaches
            # its three matrices alone (the layer stops every other path)
            total = ce + aux_loss_weight * aux + terms.get("indexer_kl", 0.0)
            if sows and (ahead := collect_sown(mods, "mtp_logits")):
                # the module's logits at position i against the token after
                # next; the row's last position has none
                with jax.named_scope("mtp"), jax.named_scope("loss"):
                    terms["mtp_loss"] = lm_loss(ahead[0][:, :-1],
                                                tg_mb[:, 1:])
                total = total + mtp_weight * terms["mtp_loss"]
            return total, (ce, acc, aux, terms, loads, handed)

        def grad_fn(*args):
            # the scopes of train/step.py, for the same split of a profile
            with jax.named_scope("fwd_bwd"):
                return jax.value_and_grad(loss_fn, has_aux=True)(*args)

        if grad_accum_steps > 1:
            # Microbatch accumulation over the local batch dim (lax.scan) —
            # same semantics as ddw_tpu.train.step.accumulate_grads; the
            # sequence dim stays whole so SP ring hops see full local shards.
            b = inputs.shape[0]
            if b % grad_accum_steps:
                raise ValueError(f"local batch {b} not divisible by "
                                 f"grad_accum_steps {grad_accum_steps}")
            mb = b // grad_accum_steps
            s = inputs.shape[1]

            def body(carry, xs):
                gsum, ssum = carry
                in_i, tg_i, idx = xs
                (_, stats), g = grad_fn(
                    state.params, in_i, tg_i,
                    jax.random.fold_in(dropout_rng, idx))
                return (jax.tree.map(jnp.add, gsum, g),
                        jax.tree.map(jnp.add, ssum, stats)), None

            stats_like = jax.eval_shape(
                lambda: loss_fn(state.params, inputs[:mb], targets[:mb],
                                dropout_rng)[1])
            (gsum, ssum), _ = lax.scan(
                body,
                (jax.tree.map(jnp.zeros_like, state.params),
                 jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype),
                              stats_like)),
                (inputs.reshape(grad_accum_steps, mb, s),
                 targets.reshape(grad_accum_steps, mb, s),
                 jnp.arange(grad_accum_steps)))
            inv = 1.0 / grad_accum_steps
            grads = jax.tree.map(lambda g: g * inv, gsum)
            loss, acc, aux, terms, loads, handed = jax.tree.map(
                lambda x: x * inv, ssum)
        else:
            (_, (loss, acc, aux, terms, loads, handed)), grads = grad_fn(
                state.params, inputs, targets, dropout_rng)
        with jax.named_scope("grad_sync"):
            if fused:
                # the scalar means ride in the flat buffer of the small
                # gradients: the step holds no synchronous reduce, which
                # would run inside an asynchronous one's window
                grads, (loss, acc, aux, terms) = grad_mean(
                    (grads, (loss, acc, aux, terms)), axes)
            else:
                grads = lax.pmean(grads, axes)
        scalar_mean = (lambda x: x) if fused else (
            lambda x: lax.pmean(x, axes))
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            if buffers:
                from ddw_tpu.models.moe import step_router_bias

                buffers = step_router_bias(
                    buffers, loads, model.layer.router_bias_rate,
                    lambda load: lax.pmean(load, axes))
        metrics = {"loss": scalar_mean(loss), "accuracy": scalar_mean(acc)}
        if getattr(model, "num_experts", 0) > 0:
            metrics["aux_loss"] = scalar_mean(aux)
        if terms:
            # what the layers sowed, under one key: the loop fetches whatever
            # is there once an epoch (train/loop.py)
            metrics["layers"] = {k: scalar_mean(v) for k, v in terms.items()}
        if handed:
            # [layers, this shard's rows of the sown value, ...]: every
            # shard's, in the mesh's order
            metrics["handed"] = {
                k: lax.all_gather(v, axes, axis=1, tiled=True)
                for k, v in handed.items()}
        return TrainState(new_params, buffers, new_opt,
                          state.step + 1), metrics

    return _step


def make_lm_train_chain(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: str | None = "seq",
    donate: bool = True,
    aux_loss_weight: float = 0.01,
    grad_accum_steps: int = 1,
    mtp_weight: float = 0.1,
    exit_entropy_weight: float = 0.05,
) -> Callable:
    """Fused K-step LM train program (``TrainCfg.steps_per_dispatch``): the
    :func:`make_lm_train_step` body ``lax.scan``-ned over a stacked token
    super-batch ``inputs/targets[K, global_batch, global_seq]`` (tokens shard
    ``P(None, data_axis, seq_axis)``; the chain dim stays unsharded). Metrics
    come back as ``[K]`` per-step arrays fetched once per chain; TrainState
    and the super-batch donate through the program. K is read from the input
    shape — one callable serves the full and the trailing partial chain."""
    tx = _maybe_lora_tx(model, tx)
    axes, sows = _lm_axes(model, data_axis, seq_axis)
    options = data_parallel_compile_options(mesh, axes, model)
    body = _make_lm_step_body(model, tx, axes, sows, options is not None,
                              aux_loss_weight, grad_accum_steps,
                              mtp_weight=mtp_weight,
                              exit_entropy_weight=exit_entropy_weight)

    def _chain(state: TrainState, inputs, targets, rng):
        def scanned(st, xs):
            in_i, tg_i = xs
            return body(st, in_i, tg_i, rng)

        return lax.scan(scanned, state, (inputs, targets))

    tok_spec = P(data_axis) if seq_axis is None else P(data_axis, seq_axis)
    sup_spec = P(None, *tok_spec)
    smapped = shard_map(
        _chain, mesh=mesh,
        in_specs=(P(), sup_spec, sup_spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    chain = jax.jit(smapped, donate_argnums=(0, 1, 2) if donate else (),
                    compiler_options=options)
    chain.batch_sharding = NamedSharding(mesh, tok_spec)  # type: ignore[attr-defined]
    chain.super_batch_sharding = NamedSharding(mesh, sup_spec)  # type: ignore[attr-defined]
    chain.place_state = replicated_placer(mesh, donate)  # type: ignore[attr-defined]
    return chain


def make_lm_eval_step(model, mesh: Mesh, data_axis: str = "data",
                      seq_axis: str | None = "seq") -> Callable:
    """Jitted eval step: world-averaged (loss, token accuracy)."""
    axes = (data_axis,) if seq_axis is None else (data_axis, seq_axis)

    def _eval(state: TrainState, inputs, targets):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["buffers"] = state.batch_stats
        logits = model.apply(variables, inputs, train=False)
        loss = lm_loss(logits, targets)
        acc = jnp.mean((jnp.argmax(logits, -1) == targets).astype(jnp.float32))
        return {"loss": lax.pmean(loss, axes), "accuracy": lax.pmean(acc, axes)}

    tok_spec = P(data_axis) if seq_axis is None else P(data_axis, seq_axis)
    smapped = shard_map(
        _eval, mesh=mesh,
        in_specs=(P(), tok_spec, tok_spec),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(smapped)
