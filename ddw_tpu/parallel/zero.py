"""ZeRO-family sharding over the data axis (GSPMD formulation).

Not in the reference — its optimizer state is fully replicated (SURVEY.md §2d
"ZeRO/FSDP-style optimizer sharding: NO") — but sharded training state is a
first-class capability of this framework: Adam moments are 2x the param bytes,
and on a data-parallel mesh each replica only needs 1/N of them.

TPU-idiomatic formulation (the scaling-book recipe): annotate the state leaves
with shardings that split their largest divisible dimension over the data
axis, and let XLA's GSPMD partitioner derive the communication schedule
instead of hand-writing it:

- **ZeRO-1** (``make_zero_train_step``): params and batch replicated,
  optimizer-state leaves sharded. The gradient all-reduce becomes
  reduce-scatter into the moment shards, each device updates only its slice,
  and the parameter update all-gathers back to replicated. Because the
  reduce-scatter happens as gradients feed the sharded moments *inside* the
  compiled step, full gradients never persist per-device — the formulation
  also delivers ZeRO-2's gradient-memory behavior for free.
- **ZeRO-3 / FSDP** (``make_fsdp_train_step``): params AND optimizer state
  sharded; each device holds 1/N of the model. GSPMD inserts per-layer
  all-gathers where the forward/backward consume full weights (weights are
  transient, not resident) and reduce-scatters gradients into the param/
  moment shards — the FSDP schedule, compiler-emitted.

Leaves with no dimension divisible by the axis size (e.g. 3x3 conv kernels
with leading dim 3) stay replicated — correctness is unaffected, only their
memory saving is forfeited. ``zero_fraction_sharded`` reports the coverage.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ddw_tpu.runtime.mesh import DATA_AXIS
from ddw_tpu.train.step import TrainState, apply_gradients, forward_and_grads


def _leaf_spec(shape: tuple[int, ...], n: int, axis: str,
               exclude: frozenset[int] = frozenset()) -> P:
    """Shard the largest dimension divisible by ``n``; replicate if none.
    ``exclude`` marks dims already owned by another axis (the 2D path)."""
    best = None
    for d, s in enumerate(shape):
        if d in exclude:
            continue
        if s % n == 0 and s >= n and (best is None or s > shape[best]):
            best = d
    if best is None:
        return P()
    spec = [None] * len(shape)
    spec[best] = axis
    return P(*spec)


def zero_state_shardings(state: TrainState, mesh: Mesh,
                         axis: str = DATA_AXIS) -> TrainState:
    """Shardings for a TrainState under ZeRO-1: params/batch_stats/step
    replicated, optimizer-state leaves sharded over ``axis``."""
    n = mesh.shape[axis]
    repl = NamedSharding(mesh, P())

    def opt_spec(leaf):
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, _leaf_spec(tuple(shape), n, axis))

    return TrainState(
        params=jax.tree.map(lambda _: repl, state.params),
        batch_stats=jax.tree.map(lambda _: repl, state.batch_stats),
        opt_state=jax.tree.map(opt_spec, state.opt_state),
        step=repl,
    )


def fsdp_state_shardings(state: TrainState, mesh: Mesh,
                         axis: str = DATA_AXIS) -> TrainState:
    """Shardings for a TrainState under ZeRO-3/FSDP: params and optimizer
    state sharded over ``axis`` (moments land on the same spec as their param
    because they share its shape), batch_stats/step replicated (they are tiny
    and BN stats are all-reduced anyway)."""
    n = mesh.shape[axis]
    repl = NamedSharding(mesh, P())

    def spec(leaf):
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, _leaf_spec(tuple(shape), n, axis))

    return TrainState(
        params=jax.tree.map(spec, state.params),
        batch_stats=jax.tree.map(lambda _: repl, state.batch_stats),
        opt_state=jax.tree.map(spec, state.opt_state),
        step=repl,
    )


def _fraction_sharded(tree, mesh: Mesh, axis: str) -> float:
    n = mesh.shape[axis]
    total = sharded = 0
    for leaf in jax.tree.leaves(tree):
        size = getattr(leaf, "size", 0)
        if not size:
            continue
        total += size
        if _leaf_spec(tuple(leaf.shape), n, axis) != P():
            sharded += size
    return sharded / total if total else 0.0


def zero_fraction_sharded(state: TrainState, mesh: Mesh,
                          axis: str = DATA_AXIS) -> float:
    """Fraction of optimizer-state elements whose leaves actually shard."""
    return _fraction_sharded(state.opt_state, mesh, axis)


def fsdp_fraction_sharded(state: TrainState, mesh: Mesh,
                          axis: str = DATA_AXIS) -> float:
    """Fraction of parameter elements whose leaves actually shard."""
    return _fraction_sharded(state.params, mesh, axis)


def fsdp_tp_state_shardings(state: TrainState, mesh: Mesh, rules,
                            axis: str = DATA_AXIS) -> TrainState:
    """2D shardings: tensor-parallel dims per ``rules`` (model axis), then
    FSDP over ``axis`` on the largest still-unsharded divisible dim of every
    param/opt leaf — the scaling-book 2D recipe (params live as [data x
    model] tiles; GSPMD emits per-layer all-gathers over ``axis`` and the
    Megatron activation reductions over the model axis).

    Works on any tree whose leaf paths end with the rule suffixes — Adam
    moments and the EMA shadow mirror param paths, so they tile identically.
    """
    from ddw_tpu.parallel.sharding import _path_key, check_spec_divisibility

    n = mesh.shape[axis]
    repl = NamedSharding(mesh, P())

    def to_sharding(path, leaf):
        key = _path_key(path)
        shape = tuple(getattr(leaf, "shape", ()))
        base = rules.spec_for(key, len(shape))
        check_spec_divisibility(key, shape, base, mesh)
        spec = list(base) + [None] * (len(shape) - len(base))
        taken = frozenset(d for d, ax in enumerate(spec) if ax is not None)
        fsdp = _leaf_spec(shape, n, axis, exclude=taken)
        for d, ax in enumerate(fsdp):
            if ax is not None:
                spec[d] = ax
        return NamedSharding(mesh, P(*spec))

    def tree_sh(tree):
        return jax.tree_util.tree_map_with_path(to_sharding, tree)

    return TrainState(
        params=tree_sh(state.params),
        batch_stats=jax.tree.map(lambda _: repl, state.batch_stats),
        opt_state=tree_sh(state.opt_state),
        step=repl,
    )


def make_fsdp_tp_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    rules,
    axis: str = DATA_AXIS,
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """2D FSDP x TP train step over a ``(data, model)`` mesh.

    Same call contract as :func:`make_fsdp_train_step`; params and optimizer
    state tile over BOTH axes (:func:`fsdp_tp_state_shardings` with e.g.
    ``ddw_tpu.parallel.sharding.VIT_TP_RULES``), the batch shards over
    ``axis``. XLA inserts the Megatron collectives over the model axis and
    the FSDP gather/reduce-scatter over the data axis from the annotations
    alone. Numerics pinned against the plain DP step.
    """
    def shardings_fn(state, mesh_, axis_):
        return fsdp_tp_state_shardings(state, mesh_, rules, axis_)

    return _make_sharded_state_step(shardings_fn, model, tx, mesh,
                                    axis, donate, grad_accum_steps)


def _global_microbatches(x, accum: int, mesh: Mesh, axis: str):
    """Split a globally-sharded batch into ``accum`` interleaved microbatches
    ``[accum, B/accum, ...]``.

    Interleaved (row i goes to microbatch ``i % accum``), not contiguous:
    the batch dim is block-sharded over ``axis``, so interleaving keeps every
    device contributing ``B/(accum*n)`` of each microbatch — the sharding
    constraint below is then a device-local transpose, no cross-device
    data movement. Any equal-size partition gives identical optimizer math
    (mean of microbatch means == full-batch mean)."""
    b = x.shape[0]
    if b % accum:
        raise ValueError(f"global batch {b} not divisible by "
                         f"grad_accum_steps {accum}")
    mb = b // accum
    n_dev = mesh.shape[axis]
    if mb % n_dev:
        raise ValueError(
            f"microbatch size {mb} (global batch {b} / grad_accum_steps "
            f"{accum}) not divisible by the '{axis}' axis size {n_dev}; the "
            f"interleaved split would force uneven sharding instead of the "
            f"device-local transpose this path guarantees")
    x = jnp.moveaxis(x.reshape(mb, accum, *x.shape[1:]), 1, 0)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(None, axis)))


def _make_sharded_step_body(model, tx: optax.GradientTransformation,
                            mesh: Mesh, axis: str, grad_accum_steps: int):
    """The single-update GSPMD body shared by the per-step stepper
    (:func:`_make_sharded_state_step`) and the fused K-step chain
    (:func:`_make_sharded_state_chain`)."""

    def _step(state: TrainState, images, labels, rng):
        dropout_rng = jax.random.fold_in(rng, state.step)
        if grad_accum_steps > 1:
            from ddw_tpu.train.step import scan_microbatches

            im = _global_microbatches(images, grad_accum_steps, mesh, axis)
            lb = _global_microbatches(labels, grad_accum_steps, mesh, axis)
            loss, acc, new_bs, grads = scan_microbatches(
                model, state, im, lb, dropout_rng)
        else:
            loss, acc, new_bs, grads = forward_and_grads(
                model, state, images, labels, dropout_rng)
        # No explicit psum: GSPMD derives the collective schedule from the
        # state shardings. ZeRO-1 (params replicated, moments sharded):
        # gradients reduce-scatter into the moment shards, the param update
        # all-gathers back to replicated. FSDP (params sharded too): per-layer
        # all-gathers where fwd/bwd consume full weights, reduce-scatter of
        # gradients into the param/moment shards.
        new_state = apply_gradients(state, tx, grads, new_bs)
        return new_state, {"loss": loss, "accuracy": acc}

    return _step


def _held(jits: dict) -> int:
    """The executables the jitted functions of ``jits`` hold, by their own
    count, summed over the state structures met: what a plain jitted step's
    ``_cache_size()`` says, and the loop's ``step_variants`` reads."""
    return sum(fn._cache_size() for fn in jits.values())


def _make_sharded_state_step(
    shardings_fn,
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DATA_AXIS,
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """Shared factory behind the ZeRO-1 and FSDP steps: a jit'd DP step whose
    TrainState in/out shardings come from ``shardings_fn(state, mesh, axis)``;
    GSPMD derives the collective schedule from those annotations.
    ``grad_accum_steps > 1`` scans interleaved global microbatches
    (:func:`_global_microbatches`) — 1/accum the activation memory, the same
    optimizer math, and each microbatch's gradients reduce-scatter straight
    into the sharded accumulator."""
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(axis))

    _step = _make_sharded_step_body(model, tx, mesh, axis, grad_accum_steps)

    def place_state(state: TrainState) -> TrainState:
        sh = shardings_fn(state, mesh, axis)
        return jax.tree.map(jax.device_put, state, sh)

    # Built per state structure+shapes: the in/out shardings are derived from
    # the concrete TrainState, so a structurally different state (different
    # optimizer/model, restored checkpoint with extra leaves) must get its own
    # jit instead of hitting a stale-sharding pytree mismatch.
    _jits: dict = {}

    def stepper(state, images, labels, rng):
        key = (jax.tree.structure(state),
               tuple(tuple(l.shape) for l in jax.tree.leaves(state)))
        fn = _jits.get(key)
        if fn is None:
            state_sh = shardings_fn(state, mesh, axis)
            fn = _jits[key] = jax.jit(
                _step,
                in_shardings=(state_sh, batch_sh, batch_sh, repl),
                out_shardings=(state_sh, repl),
                donate_argnums=(0,) if donate else (),
            )
        return fn(state, images, labels, rng)

    stepper.place_state = place_state  # type: ignore[attr-defined]
    stepper.batch_sharding = batch_sh  # type: ignore[attr-defined]
    stepper._cache_size = lambda: _held(_jits)  # type: ignore[attr-defined]
    return stepper


def _make_sharded_state_chain(
    shardings_fn,
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DATA_AXIS,
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """Fused K-step chain over a sharded TrainState — the ZeRO/FSDP analog of
    :func:`ddw_tpu.train.step.make_train_chain`. ``lax.scan`` iterates the
    GSPMD step body K times inside one jit; each scanned step's gradients
    reduce-scatter straight into the sharded moments (and, under FSDP, the
    sharded params) exactly as the per-step program's do. The super-batch
    ``[K, B, ...]`` shards its batch dim over ``axis`` (chain dim unsharded);
    the TrainState donates (in-place param/moment aliasing — the buffers that
    matter at ZeRO scale). K comes from the input shape — one callable serves
    the full and the trailing partial chain lengths."""
    repl = NamedSharding(mesh, P())
    sup_sh = NamedSharding(mesh, P(None, axis))

    body = _make_sharded_step_body(model, tx, mesh, axis, grad_accum_steps)

    def _chain(state: TrainState, images, labels, rng):
        def scanned(st, xs):
            im, lb = xs
            return body(st, im, lb, rng)

        return jax.lax.scan(scanned, state, (images, labels))

    def place_state(state: TrainState) -> TrainState:
        sh = shardings_fn(state, mesh, axis)
        return jax.tree.map(jax.device_put, state, sh)

    # Keyed per state structure+shapes like the per-step stepper: the in/out
    # shardings are derived from the concrete TrainState.
    _jits: dict = {}

    def chain(state, images, labels, rng):
        key = (jax.tree.structure(state),
               tuple(tuple(l.shape) for l in jax.tree.leaves(state)))
        fn = _jits.get(key)
        if fn is None:
            state_sh = shardings_fn(state, mesh, axis)
            # Donate the STATE only: under explicit in_shardings lowering,
            # scan xs (the super-batch) can never alias an output, so jit
            # would warn "donated buffers were not usable" on every compile
            # — the no-copy-on-donate contract tests/test_chain.py pins. The
            # state aliases fully (params/moments update in place).
            fn = _jits[key] = jax.jit(
                _chain,
                in_shardings=(state_sh, sup_sh, sup_sh, repl),
                out_shardings=(state_sh, repl),
                donate_argnums=(0,) if donate else (),
            )
        return fn(state, images, labels, rng)

    chain.place_state = place_state  # type: ignore[attr-defined]
    chain._cache_size = lambda: _held(_jits)  # type: ignore[attr-defined]
    chain.batch_sharding = NamedSharding(mesh, P(axis))  # per-step batches
    chain.super_batch_sharding = sup_sh  # type: ignore[attr-defined]
    return chain


def make_zero_train_chain(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DATA_AXIS,
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """Fused K-step chain with ZeRO-1 sharded optimizer state — same call
    contract as :func:`ddw_tpu.train.step.make_train_chain` but the moments
    live sharded (call ``chain.place_state(state)`` once, or reuse the
    per-step stepper's placement). Training result is identical to K
    sequential :func:`make_zero_train_step` dispatches (tests/test_chain.py)."""
    return _make_sharded_state_chain(zero_state_shardings, model, tx, mesh,
                                     axis, donate, grad_accum_steps)


def make_fsdp_train_chain(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DATA_AXIS,
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """Fused K-step chain with ZeRO-3/FSDP fully-sharded params + optimizer
    state; the per-layer all-gather / reduce-scatter schedule repeats inside
    the scan exactly as across K separate dispatches."""
    return _make_sharded_state_chain(fsdp_state_shardings, model, tx, mesh,
                                     axis, donate, grad_accum_steps)


def make_zero_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DATA_AXIS,
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """DP train step with ZeRO-1 sharded optimizer state.

    Same call contract as :func:`ddw_tpu.train.step.make_train_step` (state,
    images, labels, rng) -> (state, metrics) with the batch sharded over
    ``axis`` — but optimizer moments live sharded; call
    ``step.place_state(state)`` once before the first step.

    Semantics differences vs the shard_map DP step: (1) BatchNorm models
    normalize over the **global** batch here (sync-BN — XLA inserts per-layer
    mean/var all-reduces), not per local shard; statistically stronger but
    costs per-layer collectives. (2) Dropout masks are drawn from one stream
    over the global batch, not per-replica folded streams. Both steps are
    correct DP training; bit-exact equivalence with ``make_train_step`` holds
    for stateless-norm models at dropout=0 (what the equivalence test pins).
    """
    return _make_sharded_state_step(zero_state_shardings, model, tx, mesh,
                                    axis, donate, grad_accum_steps)


def make_fsdp_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis: str = DATA_AXIS,
    donate: bool = True,
    grad_accum_steps: int = 1,
) -> Callable:
    """DP train step with ZeRO-3/FSDP fully-sharded params + optimizer state.

    Same call contract and sync-BN/dropout semantics as
    :func:`make_zero_train_step`; additionally every divisible parameter leaf
    lives sharded over ``axis``, so per-device residency is ~1/N of the model
    plus transient all-gathered weights during the step (GSPMD inserts the
    per-layer all-gather/reduce-scatter pairs). Numerically identical to the
    ZeRO-1 and plain-DP steps for stateless-norm models at dropout=0 (pinned
    by the equivalence tests) — sharding placement does not change the math.
    """
    return _make_sharded_state_step(fsdp_state_shardings, model, tx, mesh,
                                    axis, donate, grad_accum_steps)
