"""Jitted SPMD train/eval steps — the TF/Keras fit inner loop + Horovod
DistributedOptimizer, collapsed into one compiled program.

The reference's per-batch hot loop is: forward/backward in TF, then Horovod's
background C++ thread fuses gradient tensors and ring-allreduces them
(``Part 1 - Distributed Training/03_model_training_distributed.py:302``; stack in
SURVEY.md §3.3). Here the entire step — forward, backward, gradient ``pmean`` over
the ``data`` mesh axis, optimizer update — is a single ``shard_map``-ped, jitted XLA
program: the collective is compiled into the step (no daemon, no fusion buffer). XLA
does NOT overlap the allreduce with the backward pass on its own: on more than one
TPU :mod:`ddw_tpu.parallel.collectives` hands it a reduce a leaf and the compiler
options under which each runs as an asynchronous fusion beneath the backward pass.

Design choices, TPU-first:
- per-device batch is the loader's per-worker batch; loss/metrics are computed
  locally then ``pmean``-ed (MetricAverageCallback semantics, reference ``:313``);
- params live replicated (the reference replicates them too — no ZeRO, SURVEY §2d);
  gradient ``pmean`` keeps them in lockstep, and a debug-mode cross-host checksum
  (``TrainCfg.debug_cross_host_checks``) asserts it — the SPMD race-detector analog
  (SURVEY §5);
- learning rate is a *dynamic* optax hyperparameter (``inject_hyperparams``), so the
  Python-side callback suite (warmup / plateau — reference ``:318-321``) can set it
  per epoch without recompiling;
- frozen-base transfer mode masks optimizer updates on the ``backbone`` param
  subtree (Keras ``trainable=False`` role, reference
  ``02_model_training_single_node.py:169``) — frozen params get ``set_to_zero``;
- dropout rng is folded with the data-axis index so replicas draw independent masks
  over their distinct shards.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddw_tpu.utils.config import ModelCfg, TrainCfg


@flax.struct.dataclass
class TrainState:
    params: Any
    batch_stats: Any          # {} for stateless-norm models
    opt_state: Any
    step: jnp.ndarray         # i32 scalar


def token_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Each item's sparse categorical cross-entropy from its logits."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Sparse categorical cross-entropy from logits (reference
    ``02_model_training_single_node.py:202`` — ``from_logits=True``)."""
    return token_cross_entropy(logits, labels).mean()


def _base_optimizer(name: str, learning_rate,
                    weight_decay: float = 0.0,
                    moment_dtype: str = "float32") -> optax.GradientTransformation:
    if weight_decay and name != "adamw":
        # refuse-loudly: silently training without the requested
        # regularization is only discoverable by comparing results
        raise ValueError(f"weight_decay is only implemented for "
                         f"optimizer='adamw', got {name!r}")
    if moment_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unknown moment_dtype {moment_dtype!r}; "
                         f"use 'float32' or 'bfloat16'")
    # bf16 first moments halve Adam's mu bytes (mu tracks the gradient scale,
    # where bf16's 8 mantissa bits suffice; nu feeds a rsqrt and stays f32 —
    # optax's mu_dtype draws exactly this line). sgd momentum is a mu too.
    mu = None if moment_dtype == "float32" else jnp.bfloat16
    if name == "adam":
        return optax.adam(learning_rate, mu_dtype=mu)
    if name == "adamw":
        return optax.adamw(learning_rate, weight_decay=weight_decay,
                           mu_dtype=mu)
    if name == "adadelta":
        if mu is not None:
            raise ValueError("moment_dtype='bfloat16' is not supported for "
                             "adadelta (its accumulators feed rsqrt like "
                             "Adam's nu) — use adam/adamw/sgd or drop the "
                             "flag")
        return optax.adadelta(learning_rate)
    if name == "sgd":
        return optax.sgd(learning_rate, momentum=0.9,
                         accumulator_dtype=mu)
    raise KeyError(f"unknown optimizer {name!r} "
                   f"(have adam, adamw, adadelta, sgd)")


def make_optimizer(
    cfg: TrainCfg,
    frozen_prefixes: tuple[str, ...] = (),
) -> optax.GradientTransformation:
    """Optimizer with dynamic LR + frozen-subtree masking.

    The returned transformation exposes ``opt_state.hyperparams['learning_rate']``
    for the callback suite. ``frozen_prefixes`` are top-level param-tree keys
    excluded from updates (transfer-learning mode).
    """
    # Validate eagerly: inject_hyperparams defers the inner factory to
    # tx.init, which would move these refusals from config time to the first
    # step — after the user already believes the run is configured.
    _base_optimizer(cfg.optimizer, 0.0, getattr(cfg, "weight_decay", 0.0),
                    getattr(cfg, "moment_dtype", "float32"))
    @functools.partial(optax.inject_hyperparams, static_args=())
    def _make(learning_rate):
        base = _base_optimizer(cfg.optimizer, learning_rate,
                               getattr(cfg, "weight_decay", 0.0),
                               getattr(cfg, "moment_dtype", "float32"))
        clip = getattr(cfg, "grad_clip_norm", 0.0)
        if clip:
            # clip BEFORE the optimizer (standard order): the global norm is
            # taken over whatever gradient tree reaches this transform
            base = optax.chain(optax.clip_by_global_norm(clip), base)
        return base

    tx = _make(learning_rate=cfg.learning_rate)
    if frozen_prefixes:
        def label_tree(params):
            return {k: ("frozen" if k in frozen_prefixes else "train") for k in params}

        tx = optax.multi_transform({"train": tx, "frozen": optax.set_to_zero()}, label_tree)
    return tx


def init_state(
    model,
    model_cfg: ModelCfg,
    train_cfg: TrainCfg,
    image_shape: tuple[int, int, int],
    rng: jax.Array,
) -> tuple[TrainState, optax.GradientTransformation]:
    """Seeded init — identical on every host, which *is* the rank-0 weight broadcast
    under SPMD (BroadcastGlobalVariablesCallback role, reference ``:305-308``;
    SURVEY §5 checkpoint note)."""
    dummy = jnp.zeros((1, *image_shape), jnp.float32)
    variables = model.init({"params": rng}, dummy, train=False)
    if model_cfg.pretrained_path:
        # Transfer-learning mode (reference ``weights='imagenet'``, SURVEY §7
        # hard-part 1a): merge the converted-backbone artifact over the fresh
        # init; the head stays randomly initialized.
        from ddw_tpu.models.convert import load_pretrained

        variables = load_pretrained(variables, model_cfg.pretrained_path)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    frozen = type(model).frozen_prefixes(getattr(model, "freeze_base", False))
    if getattr(model, "lora_rank", 0):
        # LoRA is its own freezing discipline (adapters + head train, base
        # frozen at leaf granularity) — same altitude as frozen_prefixes, and
        # mutually exclusive with it: stacking both would freeze the adapters
        # too and nest MultiTransformStates under the LR callbacks.
        if frozen:
            raise ValueError(
                "freeze_base and lora_rank are mutually exclusive — LoRA "
                "already freezes the base; set model.freeze_base=false")
        from ddw_tpu.models.lora import lora_optimizer

        tx = lora_optimizer(make_optimizer(train_cfg))
    else:
        tx = make_optimizer(train_cfg, frozen)
    if getattr(train_cfg, "ema_decay", 0.0):
        # outermost wrap: the shadow tracks the FINAL post-mask updates
        tx = with_param_ema(tx, train_cfg.ema_decay)
    opt_state = tx.init(params)
    return TrainState(params, batch_stats, opt_state, jnp.zeros((), jnp.int32)), tx


class EmaState(NamedTuple):
    """Opt-state wrapper carrying a Polyak shadow of the parameters.

    Living inside ``opt_state`` keeps ``TrainState``'s pytree structure (and
    therefore checkpoints, donation signatures, and ZeRO sharding rules)
    unchanged whether EMA is on or off."""

    inner: Any
    shadow: Any


def with_param_ema(tx: optax.GradientTransformation,
                   decay: float) -> optax.GradientTransformation:
    """Wrap ``tx`` so every update also advances an exponential moving
    average of the post-update parameters: ``shadow = d*shadow + (1-d)*p``.
    Evaluation/serving read the shadow via :func:`ema_params` — train/eval
    weight averaging (Polyak; the Keras ``ExponentialMovingAverage``
    role) without a second params copy in ``TrainState``."""
    if not 0.0 < decay < 1.0:
        raise ValueError(f"ema decay must be in (0, 1), got {decay}")

    def init(params):
        # copy=True: astype is a no-op for f32 params and would ALIAS the
        # param buffers — a donating train step then donates the same buffer
        # twice (params and shadow) and XLA rejects the execution.
        return EmaState(tx.init(params),
                        jax.tree.map(
                            lambda x: jnp.array(x, jnp.float32, copy=True),
                            params))

    def update(updates, state, params=None):
        if params is None:
            raise ValueError("with_param_ema needs params at update time")
        updates, inner = tx.update(updates, state.inner, params)
        new_p = optax.apply_updates(params, updates)
        shadow = jax.tree.map(
            lambda s, p: decay * s + (1.0 - decay) * p.astype(jnp.float32),
            state.shadow, new_p)
        return updates, EmaState(inner, shadow)

    return optax.GradientTransformation(init, update)


def ema_params(state: TrainState):
    """The Polyak shadow params, or ``None`` when EMA is off."""
    os_ = state.opt_state
    return os_.shadow if isinstance(os_, EmaState) else None


def get_lr(state: TrainState) -> float:
    """Read the current dynamic LR out of (possibly masked/EMA) opt state."""
    os_ = state.opt_state
    if isinstance(os_, EmaState):
        os_ = os_.inner
    if isinstance(os_, optax.MultiTransformState):
        os_ = os_.inner_states["train"].inner_state
    return float(os_.hyperparams["learning_rate"])


def _rate_like(old, lr: float):
    """``lr`` as the float32 scalar that takes ``old``'s place in the state:
    where ``old`` is committed to a sharding, so is the new leaf, built from
    this process's own value (no transfer between devices, no cross-host
    check), so the step's arguments keep their placement."""
    if isinstance(old, jax.Array) and old.committed:
        return jax.make_array_from_callback((), old.sharding,
                                            lambda _: np.float32(lr))
    return jnp.asarray(lr, jnp.float32)


def set_lr(state: TrainState, lr: float) -> TrainState:
    """Set the dynamic LR (callback suite writes; no recompilation: the new
    leaf has the sharding and committedness of the one it replaces, so a
    placed state stays as its step returns it)."""
    os_ = state.opt_state
    ema = None
    if isinstance(os_, EmaState):
        ema, os_ = os_, os_.inner
    if isinstance(os_, optax.MultiTransformState):
        inner = os_.inner_states["train"]
        new_hp = dict(inner.inner_state.hyperparams)
        new_hp["learning_rate"] = _rate_like(new_hp["learning_rate"], lr)
        new_inner_state = inner.inner_state._replace(hyperparams=new_hp)
        new_states = dict(os_.inner_states)
        new_states["train"] = inner._replace(inner_state=new_inner_state)
        new_os = os_._replace(inner_states=new_states)
    else:
        new_hp = dict(os_.hyperparams)
        new_hp["learning_rate"] = _rate_like(new_hp["learning_rate"], lr)
        new_os = os_._replace(hyperparams=new_hp)
    if ema is not None:
        new_os = ema._replace(inner=new_os)
    return state.replace(opt_state=new_os)


def forward_and_grads(model, state: TrainState, images, labels, dropout_rng):
    """Shared step core: forward, loss/accuracy, backward.

    Returns ``(loss, acc, new_batch_stats, grads)``. Used by the shard_map DP
    step here and the GSPMD ZeRO step (``ddw_tpu.parallel.zero``) so the
    training contract (loss fn, metric definitions, BN plumbing) lives once.
    """
    def loss_fn(params):
        variables = {"params": params}
        mutable = False
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
            mutable = ["batch_stats"]
        out = model.apply(
            variables, images, train=True,
            rngs={"dropout": dropout_rng},
            mutable=mutable,
        )
        logits, new_vars = out if mutable else (out, {})
        with jax.named_scope("loss"):
            loss = cross_entropy_loss(logits, labels)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return loss, (acc, new_vars.get("batch_stats", state.batch_stats))

    # named scopes (fwd_bwd, loss, grad_sync, optimizer; attention at the ops'
    # dispatch) are metadata on the operations of the compiled step: a profile
    # can be split by them (benchmark/tools/scope_shares.py)
    with jax.named_scope("fwd_bwd"):
        (loss, (acc, new_bs)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
    return loss, acc, new_bs, grads


def apply_gradients(state: TrainState, tx: optax.GradientTransformation,
                    grads, new_batch_stats) -> TrainState:
    """Shared step core: optimizer update + state advance."""
    with jax.named_scope("optimizer"):
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
    return TrainState(new_params, new_batch_stats, new_opt, state.step + 1)


def accumulate_grads(model, state: TrainState, images, labels, base_rng,
                     accum: int):
    """Microbatch gradient accumulation (``lax.scan`` over ``accum`` slices of
    the per-device batch). Same optimizer math as one big batch — mean CE over
    equal microbatches equals the full-batch mean — at 1/accum the activation
    memory; XLA compiles ONE microbatch program iterated sequentially.

    BatchNorm running stats thread through the scan carry (each microbatch
    updates them in turn, the usual framework semantics). Dropout draws an
    independent mask per microbatch (rng folded with the slice index).
    Returns ``(loss, acc, new_batch_stats, grads)`` like
    :func:`forward_and_grads`.
    """
    b = images.shape[0]
    if b % accum:
        raise ValueError(f"per-device batch {b} not divisible by "
                         f"grad_accum_steps {accum}")
    mb = b // accum
    im = images.reshape(accum, mb, *images.shape[1:])
    lb = labels.reshape(accum, mb, *labels.shape[1:])
    return scan_microbatches(model, state, im, lb, base_rng)


def scan_microbatches(model, state: TrainState, im, lb, base_rng):
    """The :func:`accumulate_grads` scan core over pre-split microbatches
    ``im/lb[accum, mb, ...]`` — exposed separately so the GSPMD ZeRO/FSDP
    steps (:mod:`ddw_tpu.parallel.zero`) can feed globally-interleaved
    splits instead of the shard_map path's per-device contiguous ones."""
    accum = im.shape[0]

    def body(carry, xs):
        bs, gsum, lsum, asum = carry
        im_i, lb_i, idx = xs
        loss, acc, nbs, grads = forward_and_grads(
            model, state.replace(batch_stats=bs), im_i, lb_i,
            jax.random.fold_in(base_rng, idx))
        gsum = jax.tree.map(jnp.add, gsum, grads)
        return (nbs, gsum, lsum + loss, asum + acc), None

    zero_g = jax.tree.map(jnp.zeros_like, state.params)
    zero = jnp.zeros((), jnp.float32)
    (new_bs, gsum, lsum, asum), _ = lax.scan(
        body, (state.batch_stats, zero_g, zero, zero),
        (im, lb, jnp.arange(accum)))
    inv = 1.0 / accum
    return lsum * inv, asum * inv, new_bs, jax.tree.map(lambda g: g * inv, gsum)


def _dp_step_body(model, tx: optax.GradientTransformation, axis_name: str,
                  grad_accum_steps: int, fused: bool, state: TrainState,
                  images, labels, rng):
    """One optimizer update on a per-device batch slice — the shard_map body
    shared by :func:`make_train_step` (one dispatch per step) and
    :func:`make_train_chain` (``lax.scan``-ned K times inside one program).
    The dropout rng folds the device counter ``state.step``, so a scanned
    step draws exactly the mask the equivalent host-dispatched step would.
    ``fused``: the builder's ``jit`` got
    :func:`~ddw_tpu.parallel.collectives.data_parallel_compile_options`, so
    the means take ``grad_mean``'s form."""
    me = lax.axis_index(axis_name)
    dropout_rng = jax.random.fold_in(jax.random.fold_in(rng, me), state.step)
    if grad_accum_steps > 1:
        loss, acc, new_bs, grads = accumulate_grads(
            model, state, images, labels, dropout_rng, grad_accum_steps)
    else:
        loss, acc, new_bs, grads = forward_and_grads(
            model, state, images, labels, dropout_rng)
    # THE collective: gradient averaging across the data axis
    # (hvd.DistributedOptimizer role, reference :302).
    with jax.named_scope("grad_sync"):
        if fused:
            # imported here: ddw_tpu.parallel's ZeRO steps import this module
            from ddw_tpu.parallel.collectives import grad_mean

            # the statistics and the scalar means ride in the flat buffer of
            # the small gradients: the step holds no synchronous reduce,
            # which would run inside an asynchronous one's window
            grads, new_bs, metrics = grad_mean(
                (grads, new_bs, {"loss": loss, "accuracy": acc}), axis_name)
        else:
            grads = lax.pmean(grads, axis_name)
            if state.batch_stats:
                # world-consistent BN statistics
                new_bs = lax.pmean(new_bs, axis_name)
            metrics = {
                "loss": lax.pmean(loss, axis_name),
                "accuracy": lax.pmean(acc, axis_name),
            }
    return apply_gradients(state, tx, grads, new_bs), metrics


def replicated_placer(mesh: Mesh, donate: bool = True) -> Callable:
    """The ``place_state`` of the plain data-parallel steps and chains (here
    and in :mod:`ddw_tpu.train.lm_step`): every leaf committed to
    ``NamedSharding(mesh, P())``, which is how those steps return their
    state. ``jit`` keys an executable on the placement of its arguments, so
    a state placed before the first call gives that call the signature of
    every later one. Leaf by leaf, and a leaf that is already on a device of
    the mesh is shared there, not copied; with ``donate`` (the step's own)
    the unplaced state is given up as the step's first call would have
    taken it."""
    repl = replicated_sharding(mesh)

    def place_state(state: TrainState) -> TrainState:
        return jax.tree.map(
            lambda x: jax.device_put(x, repl, donate=donate), state)

    return place_state


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "data",
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """Build the jitted SPMD train step over ``mesh``.

    Returns ``step(state, images, labels, rng) -> (state, metrics)`` where images /
    labels are globally-sharded arrays split along ``axis_name`` and metrics are
    already world-averaged (loss, accuracy). ``grad_accum_steps > 1`` runs each
    device's batch as that many sequential microbatches (see
    :func:`accumulate_grads`). It compiles once for each placement of its
    arguments: ``step.place_state(state)`` before the first call gives the
    state the placement the step returns it in, and one executable serves.
    """
    # imported here: ddw_tpu.parallel's ZeRO steps import this module
    from ddw_tpu.parallel.collectives import data_parallel_compile_options

    options = data_parallel_compile_options(mesh, axis_name, model)
    _step = functools.partial(_dp_step_body, model, tx, axis_name,
                              grad_accum_steps, options is not None)

    repl = P()
    data_spec = P(axis_name)
    smapped = shard_map(
        _step,
        mesh=mesh,
        in_specs=(repl, data_spec, data_spec, repl),
        out_specs=(repl, repl),
        check_vma=False,
    )
    step = jax.jit(smapped, donate_argnums=(0,) if donate else (),
                   compiler_options=options)
    step.place_state = replicated_placer(mesh, donate)  # type: ignore[attr-defined]
    return step


def make_train_chain(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "data",
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """Build the fused K-step train program: ``lax.scan`` over K optimizer
    updates inside ONE jitted/shard_map program (``TrainCfg.steps_per_dispatch``).

    ``chain(state, images, labels, rng) -> (state, metrics)`` with a stacked
    super-batch ``images[K, B, ...]`` / ``labels[K, B]`` (batch dim sharded
    over ``axis_name``, chain dim unsharded — the :class:`ShardedLoader`
    assembles it on its prefetch thread) and ``metrics['loss'|'accuracy']``
    as ``[K]`` per-step arrays fetched once per chain. One host dispatch and
    one metric fetch cover K steps — the Python-dispatch/bookkeeping cost of
    small compiled steps amortizes by ~1/K (docs/performance.md).

    K is read from the input shape, so ONE returned callable serves both the
    full chain length and a trailing partial chain (each compiles once).
    ``donate=True`` donates the TrainState AND the super-batch buffers through
    the chain. Math is identical to K host-dispatched ``make_train_step``
    calls (the scanned body folds ``state.step`` into the dropout rng exactly
    as the per-step program does) — pinned by ``tests/test_chain.py``.
    """
    from ddw_tpu.parallel.collectives import data_parallel_compile_options

    options = data_parallel_compile_options(mesh, axis_name, model)
    body = functools.partial(_dp_step_body, model, tx, axis_name,
                             grad_accum_steps, options is not None)

    def _chain(state: TrainState, images, labels, rng):
        def scanned(st, xs):
            im, lb = xs
            return body(st, im, lb, rng)

        return lax.scan(scanned, state, (images, labels))

    repl = P()
    sup_spec = P(None, axis_name)
    smapped = shard_map(
        _chain,
        mesh=mesh,
        in_specs=(repl, sup_spec, sup_spec, repl),
        out_specs=(repl, repl),
        check_vma=False,
    )
    chain = jax.jit(smapped, donate_argnums=(0, 1, 2) if donate else (),
                    compiler_options=options)
    chain.place_state = replicated_placer(mesh, donate)  # type: ignore[attr-defined]
    return chain


def chain_plan(steps_per_epoch: int, k: int) -> tuple[int, ...]:
    """Chain lengths covering one epoch *exactly*: ``steps_per_epoch // k``
    full chains plus one trailing partial chain for the remainder (the second
    — and last — shape the chain program ever compiles). ``k=1`` is today's
    per-step dispatch. Both trainers and the loader's super-batch assembly
    consume the same plan, so step accounting cannot drift."""
    if steps_per_epoch < 1:
        raise ValueError(f"steps_per_epoch must be >= 1, got {steps_per_epoch}")
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    if k <= 1:
        return (1,) * steps_per_epoch
    n_full, tail = divmod(steps_per_epoch, k)
    return (k,) * n_full + ((tail,) if tail else ())


def fetch_metrics_mean(values) -> float:
    """Exact per-step mean of accumulated device metrics with ONE dispatch +
    ONE host fetch. ``values`` mixes scalars (per-step dispatch) and ``[k]``
    chain arrays; each element of the concatenation is one training step, so
    the mean equals the old per-element ``device_get`` + ``np.mean`` exactly —
    without a blocking host round-trip per scalar."""
    if not values:
        return float("nan")
    flat = jnp.concatenate([jnp.ravel(jnp.asarray(v)) for v in values])
    return float(jax.device_get(jnp.mean(flat)))


def make_eval_step(model, mesh: Mesh, axis_name: str = "data") -> Callable:
    """Jitted eval step: world-averaged (loss, accuracy) on a sharded batch."""

    def _eval(state: TrainState, images, labels):
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, images, train=False)
        loss = cross_entropy_loss(logits, labels)
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        return {"loss": lax.pmean(loss, axis_name), "accuracy": lax.pmean(acc, axis_name)}

    smapped = shard_map(
        _eval,
        mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(smapped)


def batch_sharding(mesh: Mesh, axis_name: str = "data") -> NamedSharding:
    """Sharding for host batches: leading (batch) dim split over the data axis."""
    return NamedSharding(mesh, P(axis_name))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def params_checksum(state: TrainState) -> float:
    """Debug-mode consistency checksum (SPMD sanitizer, SURVEY §5): identical across
    hosts iff params are in lockstep."""
    leaves = jax.tree.leaves(state.params)
    return float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32))) for x in leaves))
