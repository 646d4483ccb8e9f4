"""The epoch loop both trainers run, and the set-up they share.

``Trainer.fit`` and ``LMTrainer.fit`` / ``fit_tables`` make what differs
between the families — a model, a state, a step, loaders, what a row holds
besides the means — and hand it to :func:`run_epochs`, which owns everything
from there to ``fit``'s return. The loop never builds a step: each trainer
does, through its own module's name for the factory. Above the loop, what both
set up the same way: :func:`open_fit`, :func:`open_checkpoints`,
:func:`restore`, :func:`log_fit_params`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import jax

from ddw_tpu.checkpoint.ckpt import BestCheckpointKeeper, CheckpointManager
from ddw_tpu.obs.step_scopes import step_table
from ddw_tpu.obs.trace import Tracer, chrome_trace, span_lane
from ddw_tpu.runtime.elastic import maybe_elastic_restart, process_topology
from ddw_tpu.runtime.faults import Preempted, maybe_fault, preemption_requested
from ddw_tpu.train.step import (TrainState, ema_params, fetch_metrics_mean,
                                get_lr, set_lr)
from ddw_tpu.utils.config import TrainCfg, to_dict


class _ZeroCheckpointAdapter:
    """CheckpointManager-shaped facade over the sharded per-process format
    (:mod:`ddw_tpu.checkpoint.sharded`) for ``TrainCfg.zero`` / ``fsdp``
    fits: saving a sharded TrainState through the classic manager would
    all-gather the moment shards into one host — the exact thing ZeRO exists
    to avoid. Save is collective (every process writes its shards), matching
    how the loop already calls it on every rank."""

    def __init__(self, ckpt_dir: str, mesh, axis: str, fsdp: bool = False,
                 keep: int = 3, async_write: bool = False,
                 max_inflight: int = 1):
        from ddw_tpu.checkpoint.sharded import ShardedCheckpointManager

        self._mgr = ShardedCheckpointManager(ckpt_dir, keep=keep,
                                             async_write=async_write,
                                             max_inflight=max_inflight)
        self._mesh, self._axis, self._fsdp = mesh, axis, fsdp

    def restore(self, target, step: int | None = None):
        from ddw_tpu.parallel.zero import (
            fsdp_state_shardings,
            zero_state_shardings,
        )

        fn = fsdp_state_shardings if self._fsdp else zero_state_shardings
        sh = fn(target, self._mesh, self._axis)
        return self._mgr.restore(target, sh, step)

    def __getattr__(self, name):    # save, read_metadata, latest_step, close
        return getattr(self._mgr, name)


@dataclasses.dataclass
class TrainResult:
    """What ``fit`` returns: the last epoch's validation means (NaN where no
    epoch ran), every epoch's row, the final state, and the number of the
    epoch after the last one run (0 where none ran)."""

    val_loss: float
    val_accuracy: float
    history: list[dict[str, float]]
    state: TrainState
    epochs_run: int


# -- set-up -------------------------------------------------------------------
def _profiling(cfg: TrainCfg) -> bool:
    return bool(cfg.trace_dir) and process_topology()[0] == 0


def open_fit(cfg: TrainCfg, tracer):
    """``(tracer, lane, setup_id)`` at the top of a fit: the trainer's own
    tracer or — an operator's trace being the device profile AND the span
    tree — one made for ``TrainCfg.trace_dir``; its ``tid="train"`` lane (a
    no-op lane without a tracer), on which set-up stamps each boundary once;
    and the open ``fit_setup`` span. The loaders get the tracer too."""
    if tracer is None and _profiling(cfg):
        tracer = Tracer(capacity=65536, process="train")
    lane = span_lane(tracer, "train", "train")
    return tracer, lane, lane.open()


def open_checkpoints(cfg: TrainCfg, mesh, axis: str):
    """``(ckpt, best)`` for ``cfg.checkpoint_dir`` (None, None without one):
    the epoch stream's manager and, under ``checkpoint_keep_best``, the
    keeper of ``<dir>/best``. ZeRO/FSDP states go through the sharded
    per-process format, which composes with ``async_checkpoint`` (shards are
    snapshotted to host at the boundary; per-process background writers run
    the collective commit protocol)."""
    if not cfg.checkpoint_dir:
        if cfg.checkpoint_keep_best:
            raise ValueError("checkpoint_keep_best needs a checkpoint_dir")
        return None, None

    def manager(d, **kw):
        if cfg.zero or cfg.fsdp:
            return _ZeroCheckpointAdapter(d, mesh, axis, fsdp=cfg.fsdp,
                                          async_write=cfg.async_checkpoint,
                                          **kw)
        return CheckpointManager(d, async_write=cfg.async_checkpoint, **kw)

    ckpt = manager(cfg.checkpoint_dir,
                   max_inflight=cfg.async_checkpoint_inflight)
    best = (BestCheckpointKeeper(cfg.checkpoint_dir,
                                 lambda d: manager(d, keep=1))
            if cfg.checkpoint_keep_best else None)
    return ckpt, best


def restore(ckpt, state, steps_per_epoch: int):
    """``(state, start_epoch, metadata)`` from the newest checkpoint;
    ``(state, 0, None)`` where there is none."""
    state, at_step = ckpt.restore(state)
    if at_step is None:
        return state, 0, None
    return (state, int(at_step) // steps_per_epoch,
            ckpt.read_metadata(at_step))


def place_state(step, state, lane, setup_id):
    """``state`` as ``step`` returns it (``step.place_state``), before the
    step sees it: every fit's, whatever its layout, after restore. The
    ``place_state`` child of ``fit_setup`` says how many leaves were not yet
    where the step puts them, and their bytes. A step that is not one of
    this package's (a test's double) and says nothing of its placement gets
    the state as it is, and ``step_variants`` will show what that costs."""
    t0 = time.monotonic()
    before = lane.on and [
        (x.sharding, x.committed) if isinstance(x, jax.Array) else None
        for x in jax.tree.leaves(state)]
    state = getattr(step, "place_state", lambda state: state)(state)
    args = None
    if lane.on:
        moved = [x for x, was in zip(jax.tree.leaves(state), before)
                 if was != (getattr(x, "sharding", None), True)]
        args = {"leaves": len(moved), "bytes": sum(x.nbytes for x in moved)}
    lane.span("place_state", t0, time.monotonic(), setup_id, args=args)
    return state


def log_fit_params(run, sizes: dict, **cfgs) -> None:
    """``<section>.<field>`` of each config, then the fit's sizes."""
    if run is not None:
        for section, c in cfgs.items():
            run.log_params({f"{section}.{k}": v
                            for k, v in to_dict(c).items()})
        run.log_params(sizes)


# -- the loop -----------------------------------------------------------------
def _asked(stream, ready: list):
    """The batches of an opened validation stream; before each is asked for,
    whether it waited in the stream's queue goes on ``ready``."""
    while True:
        waiting = stream.ready()
        try:
            batch = next(stream)
        except StopIteration:
            return
        ready.append(waiting)
        yield batch


def run_epochs(*, cfg: TrainCfg, state, sched, plan, start_epoch: int,
               train_batches, val_batches, step_args, run_step, eval_step,
               ckpt, best, run, tracer, setup_id, t_fit: float,
               timed_row=None, row_extra=None, on_epoch=None) -> TrainResult:
    """Epochs ``start_epoch .. cfg.epochs`` of one fit, and all that goes with
    them: the chain loop with the fault, elastic and preemption hooks,
    validation, the fetches, the row and its report, the schedule's epoch
    end, the checkpoints, the span tree on ``tid="train"``
    (docs/observability.md lists it), the hub's observations, the
    ``TrainCfg.trace_dir`` profile and the ``finally`` that joins the writers.

    ``plan`` is the epoch's chain lengths (``chain_plan``);
    ``train_batches(epoch)`` gives an iterator with one item a chain.
    ``val_batches`` gives the epoch's validation batches, and is one of two
    things. A loader (anything with ``open()``, as ``data/loader.py``'s
    ``ShardedLoader``) is opened when the epoch's training begins, right
    after the dispatch of the epoch's first chain (the chip then has work,
    also where that chain is the epoch's only one): the stream's thread reads,
    decodes and transfers the pass while the chip trains and rests on its
    full queue, so validation asks a stream that is ahead (an epoch that
    dispatches no chain opens it at validation); the stream is closed when
    validation ends and on every other way out of the epoch.
    Anything else is called, at validation, for an iterable (the in-memory
    ``LMTrainer.fit``: nothing to open).
    ``run_step`` is the trainer's step or chain and ``step_args(batch,
    host_step)`` what it is called with after the state — the batch and the
    trainer's rng rule — so ``run_step(state, *step_args(batch, host_step))
    -> (state, metrics)`` is the dispatch. The loop makes that call itself
    because, under a tracer, it first asks which layer each operation of that
    one executable belongs to (the ``step_scopes`` span, below), and at each
    epoch's end how many executables the step holds (``step_variants``);
    ``eval_step(eval_state, *batch)`` gives ``loss`` and ``accuracy``.
    ``state`` comes placed as ``run_step`` returns it (:func:`place_state`)
    and the loop keeps it so — what it writes into the state (``set_lr``)
    takes the placement of the leaf it replaces — so one executable of the
    step serves the whole fit.

    A row is ``epoch``, the four means and ``lr``, then ``row_extra`` (a
    dict), then ``timed_row(train_seconds)``, then, where the validation
    batches came from an opened stream, ``val_ready_share``: of the batches
    the loop asked for, the share that waited in the stream's queue at the
    ask (``LoaderStream.ready``; the ``epoch`` span carries it too, and each
    ``val_data_wait`` its own ``ready``). A trainer that gives
    ``timed_row`` reports the training part of the epoch as a time, so for it
    the training means are fetched before validation (``train_fetch``: the
    device drains there, and validation starts on an idle chip, with a
    ``next`` on the opened stream's queue and one dispatch); otherwise
    every mean is fetched at the epoch's one barrier, ``epoch_fetch``.
    ``on_epoch(row, state)`` runs after the schedule's epoch end, before the
    checkpoint; a true return stops the fit, an exception leaves it.

    ``setup_id`` is the open ``fit_setup`` span, begun at ``t_fit``; it ends
    where the first chain starts.

    Under a tracer the fit's first chain holds one more span, ``step_scopes``,
    between its ``data_wait`` and its ``dispatch``: the table of the step's
    executable (``obs/step_scopes.py``), made from the very arguments of the
    first dispatch before the step is loaded, so that the table is of the
    executable that runs and the device never holds two. Its arguments are
    the table, its length what the table cost. Without a tracer nothing is
    lowered or built."""
    sp = span_lane(tracer, "train", "train")
    steps_per_epoch = sum(plan)
    chained = any(k > 1 for k in plan)
    step_variants = getattr(run_step, "_cache_size", None)
    table_due = sp.on       # the step's table: once a fit, traced fits only
    # telemetry plane: a Run wrapped by obs.telemetry.tee_run exposes its
    # hub — chain dispatch and checkpoint-write latencies become windowed
    # dist series beside the serving fleet's (docs/observability.md)
    hub = getattr(run, "telemetry_hub", None) if run is not None else None
    state = sched.initial_state(state, start_epoch, start_epoch > 0)
    # The step counter is kept on the host: it equals ``state.step`` by
    # construction, and reading the device's at every hook, checkpoint or rng
    # fold would be a blocking device_get that serializes async dispatch.
    host_step = int(jax.device_get(state.step))
    # TrainCfg.trace_dir: the device profile of the first SETTLED epoch (the
    # one before it compiles), device lines only — the host tracer at its
    # default more than doubles an epoch — with the tracer's ring written
    # beside it
    profile_epoch = (min(start_epoch + 1, cfg.epochs - 1)
                     if _profiling(cfg) else -1)
    tracing = False
    history: list[dict[str, float]] = []
    # a provider with a loader behind it is opened ahead; the epoch's stream
    open_val = getattr(val_batches, "open", None)
    val_stream = None
    try:
        for epoch in range(start_epoch, cfg.epochs):
            t_epoch = time.monotonic()
            epoch_id = sp.open()
            if epoch == profile_epoch:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 0
                jax.profiler.start_trace(cfg.trace_dir,
                                         profiler_options=options)
                tracing = True
                if run is not None:
                    # The report links this param as the per-run
                    # profiler-trace artifact (Horovod-Timeline role).
                    run.log_params(
                        {"trace_dir": os.path.abspath(cfg.trace_dir)})
            t_train = time.monotonic()
            losses, accs = [], []
            # what the model's layers sowed (the step's metrics["layers"],
            # train/lm_step.py), fetched once an epoch with the losses
            counted: dict = {}
            batches = train_batches(epoch)
            step_i = 0
            for k_chain in plan:
                t_chain = time.monotonic()
                chain_id = sp.open()
                if setup_id is not None:
                    # set-up ends where the first chain starts
                    sp.span("fit_setup", t_fit, t_chain, span=setup_id)
                    setup_id = None
                # Fault-injection hook (runtime.faults): free no-op unless
                # DDW_FAULT targets this rank/step/generation. Under chained
                # dispatch it (like the preemption check and the per-batch LR
                # write below) fires at CHAIN boundaries — the host only
                # regains control every k_chain steps (docs/performance.md).
                maybe_fault("step", step=host_step,
                            ckpt_dir=cfg.checkpoint_dir or None)
                # Elastic park point (no-op outside an elastic gang): a peer
                # rank died and the gang re-formed — raise ElasticRestart
                # HERE, at the chain boundary, so this surviving process
                # re-enters fit(resume=True) from the latest durable
                # checkpoint with its pid/programs intact
                # (runtime/elastic.py). The finally block below joins the
                # async ckpt writer on the way out.
                maybe_elastic_restart(step=host_step)
                if preemption_requested():
                    # Graceful preemption (SIGTERM): checkpoint the live
                    # state mid-epoch, then leave via Preempted — the gang
                    # worker converts it to EXIT_PREEMPTED so the supervisor
                    # restarts without burning the crash budget. The finally
                    # block below joins the async writer, making the save
                    # durable.
                    if ckpt:
                        t_ck = time.monotonic()
                        ckpt.save(state, host_step,
                                  metadata={"epoch": epoch, "preempted": True,
                                            "callbacks": sched.state_dicts()})
                        sp.span("ckpt_save", t_ck, time.monotonic(), epoch_id,
                                args=sp.on and {"step": host_step})
                    raise Preempted(host_step)
                # Per-batch LR: cosine everywhere, or the Goyal warmup ramp
                # (Horovod warmup-callback granularity, reference :314-318);
                # None past warmup in the plateau regime. set_lr is a
                # dynamic-hyperparameter write — no recompilation.
                lr_b = sched.lr_for_batch(epoch, step_i, steps_per_epoch)
                if lr_b is not None:
                    state = set_lr(state, lr_b)
                t_wait = time.monotonic()
                batch = next(batches)
                t_disp = time.monotonic()
                sp.span("data_wait", t_wait, t_disp, chain_id,
                        args=sp.on and {"step": host_step})
                rest = step_args(batch, host_step)
                if table_due:
                    table_due = False
                    table = step_table(run_step, (state, *rest))
                    if table is not None:
                        t_table, t_disp = t_disp, time.monotonic()
                        sp.span("step_scopes", t_table, t_disp, chain_id,
                                args=table)
                # chained: a [k, B, ...] super-batch through the fused scan
                # program; metrics come back as [k] per-step arrays — no
                # per-step host work at all
                state, metrics = run_step(state, *rest)
                t_end = time.monotonic()
                # enqueue plus back-pressure from the device queue
                sp.span("dispatch", t_disp, t_end, chain_id,
                        args=sp.on and {"step": host_step, "k": k_chain})
                losses.append(metrics["loss"])
                accs.append(metrics["accuracy"])
                for name, value in metrics.get("layers", {}).items():
                    counted.setdefault(name, []).append(value)
                # the chain boundary as the host sees it (device time for the
                # chain lives in the jax.profiler trace, not here); its self
                # time, less data_wait and dispatch, is the loop's own work
                sp.span("train_chain", t_chain, t_end, epoch_id, chain_id,
                        args=sp.on and {"epoch": epoch, "step": host_step,
                                        "k": k_chain, "chained": chained})
                if hub is not None:
                    hub.observe("train.chain_ms", (t_end - t_chain) * 1e3)
                if open_val is not None and val_stream is None:
                    # the epoch's first chain is on the device: the
                    # validation pass is made from here on, behind the
                    # training (opened before that dispatch, its thread
                    # would take the host from an idle chip: 1.5 ms an epoch
                    # in the LM cells, PERF.md section 6, PR 40)
                    val_stream = open_val()
                host_step += k_chain
                step_i += k_chain

            # ONE device reduction + fetch per metric for the whole epoch
            # (fetch_metrics_mean) instead of a device_get per scalar — exact
            # per-step mean whether entries are scalars or [k] chain arrays.
            t_val = time.monotonic()
            timed = {}
            if timed_row is not None:
                train_loss = fetch_metrics_mean(losses)
                train_acc = fetch_metrics_mean(accs)
                t_f, t_val = t_val, time.monotonic()
                sp.span("train_fetch", t_f, t_val, epoch_id)
                timed = timed_row(t_val - t_train)

            vlosses, vaccs = [], []
            val_id = sp.open()
            # ZeRO/FSDP: eval reads only params/batch_stats — pass the state
            # without the sharded moments or the eval jit would all-gather
            # them to match its replicated in_spec (FSDP params do get
            # gathered — eval wants full weights)
            eval_state = (state.replace(opt_state=())
                          if cfg.zero or cfg.fsdp else state)
            if cfg.ema_decay:
                # evaluate the Polyak shadow (what serving should ship)
                eval_state = eval_state.replace(params=ema_params(state),
                                                opt_state=())
            t0v = t_val
            ready = []
            if open_val is not None and val_stream is None:
                # an epoch that dispatched no chain: nothing to work behind
                val_stream = open_val()
            for i, vbatch in enumerate(
                    val_batches() if open_val is None
                    else _asked(val_stream, ready)):
                t1v = time.monotonic()
                sp.span("val_data_wait", t0v, t1v, val_id,
                        args=sp.on and {"i": i, "first": i == 0,
                                        **({"ready": ready[i]} if ready
                                           else {})})
                m = eval_step(eval_state, *vbatch)
                vlosses.append(m["loss"])
                vaccs.append(m["accuracy"])
                t0v = time.monotonic()
                sp.span("val_dispatch", t1v, t0v, val_id,
                        args=sp.on and {"i": i})
            sp.span("validation", t_val, t0v, epoch_id, val_id,
                    args=sp.on and {"steps": len(vlosses)})
            if val_stream is not None:
                val_stream.close()
                val_stream = None
            ahead = ({"val_ready_share": sum(ready) / len(ready)} if ready
                     else {})
            # The first fetch here is the epoch's barrier: it returns when
            # the device has run every step before it.
            if timed_row is None:
                train_loss = fetch_metrics_mean(losses)
                train_acc = fetch_metrics_mean(accs)
            row = {"epoch": epoch, "loss": train_loss, "accuracy": train_acc,
                   "val_loss": fetch_metrics_mean(vlosses),
                   "val_accuracy": fetch_metrics_mean(vaccs),
                   "lr": get_lr(state), **(row_extra or {}), **timed,
                   **ahead,
                   **{name: fetch_metrics_mean(values)
                      for name, values in counted.items()}}
            t_rep = time.monotonic()
            sp.span("epoch_fetch", t0v, t_rep, epoch_id)
            if tracing:
                # after the barrier, so the profile holds the epoch's
                # validation and every device operation of it
                jax.profiler.stop_trace()
                tracing = False
            history.append(row)
            if run is not None:
                run.log_metrics({k: v for k, v in row.items() if k != "epoch"},
                                step=epoch)
            t_cb = time.monotonic()
            sp.span("epoch_report", t_rep, t_cb, epoch_id)

            end_id = sp.open()
            # LR-plateau AFTER metrics are world-consistent (ordering
            # contract, reference :310-313 — trivially satisfied: metrics are
            # pmean-ed in-step)
            state, stop = sched.epoch_end(state, row["val_loss"], epoch)
            if on_epoch is not None and on_epoch(row, state):
                stop = True
            # Checkpoint AFTER the callbacks consumed this epoch's metrics,
            # so the saved counters (and any plateau LR cut) are exactly the
            # state the next epoch starts from — resume = continuation
            # (ScheduleSuite holds the ordering rules).
            if ckpt and (epoch + 1) % cfg.checkpoint_every_epochs == 0:
                t_ck = time.monotonic()
                ckpt.save(state, host_step,
                          metadata={"epoch": epoch,
                                    "val_loss": row["val_loss"],
                                    "val_accuracy": row["val_accuracy"],
                                    "callbacks": sched.state_dicts(),
                                    "metrics": row})
                t1 = time.monotonic()
                sp.span("ckpt_save", t_ck, t1, end_id,
                        args=sp.on and {"step": host_step})
                if hub is not None:
                    hub.observe("train.ckpt_write_ms", (t1 - t_ck) * 1e3)
            if best is not None:
                best.maybe_save(state, host_step, row, {"epoch": epoch})
            t1 = time.monotonic()
            sp.span("epoch_end", t_cb, t1, epoch_id, end_id)
            # step_variants: the executables jit holds of the step; more
            # than one means something took the state out of its placement,
            # and that the step_scopes table, which is of the first
            # dispatch's executable, is of one of several (making the table
            # adds none: it compiles ahead of time, outside jit's own cache)
            sp.span("epoch", t_epoch, t1, span=epoch_id,
                    args=sp.on and {"epoch": epoch, "steps": steps_per_epoch,
                                    "step_variants": step_variants
                                    and step_variants(), **ahead,
                                    **{name: row[name] for name in counted}})
            if epoch == profile_epoch:
                # the spans of everything so far, the profiled epoch whole,
                # in the form Perfetto loads beside the profile
                with open(os.path.join(cfg.trace_dir,
                                       "train_spans.trace.json"), "w") as f:
                    json.dump(chrome_trace(tracer.drain()), f)
            if stop:
                break
    finally:
        # Always runs — including the documented abort path where on_epoch /
        # a pruner raises out of fit (examples 04/05): the async ckpt writer
        # thread is joined and released, and any in-flight background write
        # error surfaces here rather than being dropped; a dangling profiler
        # trace is closed.
        try:
            if tracing:
                jax.profiler.stop_trace()
        finally:
            # unconditional even if stop_trace raises: the writer thread
            # must be joined either way, and an epoch left before its
            # validation (Preempted, ElasticRestart, an exception in the
            # step) leaves no producer thread and no device batch behind
            if val_stream is not None:
                val_stream.close()
            if ckpt is not None:
                ckpt.close()
            if best is not None:
                best.close()
    last = history[-1] if history else {"val_loss": float("nan"),
                                        "val_accuracy": float("nan"),
                                        "epoch": -1}
    return TrainResult(last["val_loss"], last["val_accuracy"], history, state,
                       last["epoch"] + 1)
