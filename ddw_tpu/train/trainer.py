"""Trainer — the ``model.fit`` + ``train_and_evaluate_hvd`` orchestration.

Reproduces the distributed-DP contract of SURVEY.md §2b (reference
``Part 1 - Distributed Training/03_model_training_distributed.py:282-375``) on a
JAX device mesh:

1.  process bootstrap       -> ``runtime.initialize_distributed`` (done by caller/launcher)
2.  tracking plumbing       -> a shared-filesystem :class:`ddw_tpu.tracking.Tracker` run
3.  device pinning          -> inherent (each process owns its local TPU chips)
4.  LR x world scaling      -> ``TrainCfg.scale_lr_by_world`` (reference ``:301``)
5.  DistributedOptimizer    -> gradient ``pmean`` inside the jitted step
6.  callback suite          -> :mod:`ddw_tpu.train.callbacks` (warmup ``:318``,
                               plateau ``:321``; metric averaging is inside the step)
7.  (TF2 compile quirk)     -> n/a under jit
8.  shard-by-rank loading   -> :class:`ShardedLoader` (cur_shard=process, infinite repeat)
9.  step accounting         -> ``train_size // (batch * world)`` (reference ``:350-351``)
10. rank-0 logging + return -> tracker writes on process 0; returns (val_loss, val_acc)

"Worker" in the reference = one Horovod process = one accelerator. Here the data
axis of the mesh plays that role: global batch = ``batch_size * mesh.shape['data']``
(batch-per-worker semantics, reference ``:81``), fed per host by a loader shard.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import time
from typing import Any

import jax

from ddw_tpu.checkpoint.ckpt import CheckpointManager
from ddw_tpu.data.loader import ShardedLoader
from ddw_tpu.data.store import Table
from ddw_tpu.models.registry import build_model
from ddw_tpu.obs.trace import Tracer, chrome_trace, span_lane
from ddw_tpu.runtime.elastic import maybe_elastic_restart, process_topology
from ddw_tpu.runtime.faults import Preempted, maybe_fault, preemption_requested
from ddw_tpu.runtime.mesh import make_data_mesh, make_mesh, MeshSpec, DATA_AXIS
from ddw_tpu.tracking.tracker import Run
from ddw_tpu.train.schedule import ScheduleSuite
from ddw_tpu.train.step import (
    TrainState,
    batch_sharding,
    chain_plan,
    ema_params,
    fetch_metrics_mean,
    get_lr,
    init_state,
    make_eval_step,
    make_train_chain,
    make_train_step,
    params_checksum,
    set_lr,
)
from ddw_tpu.utils.config import DataCfg, ModelCfg, TrainCfg, to_dict


class _ZeroCheckpointAdapter:
    """CheckpointManager-shaped facade over the sharded per-process format
    (:mod:`ddw_tpu.checkpoint.sharded`) for ``TrainCfg.zero`` fits: saving a
    ZeRO-sharded TrainState through the classic manager would all-gather the
    moment shards into one host — the exact thing ZeRO exists to avoid. Save
    is collective (every process writes its shards), matching how the trainer
    already calls it on every rank."""

    def __init__(self, ckpt_dir: str, mesh, axis: str, fsdp: bool = False,
                 keep: int = 3, async_write: bool = False,
                 max_inflight: int = 1):
        from ddw_tpu.checkpoint.sharded import ShardedCheckpointManager

        self._mgr = ShardedCheckpointManager(ckpt_dir, keep=keep,
                                             async_write=async_write,
                                             max_inflight=max_inflight)
        self._mesh, self._axis, self._fsdp = mesh, axis, fsdp

    def save(self, state, step: int, metadata: dict | None = None):
        return self._mgr.save(state, step, metadata)

    def restore(self, target, step: int | None = None):
        from ddw_tpu.parallel.zero import (
            fsdp_state_shardings,
            zero_state_shardings,
        )

        fn = fsdp_state_shardings if self._fsdp else zero_state_shardings
        sh = fn(target, self._mesh, self._axis)
        return self._mgr.restore(target, sh, step)

    def read_metadata(self, step: int | None = None):
        return self._mgr.read_metadata(step)

    def latest_step(self):
        return self._mgr.latest_step()

    def wait(self) -> None:
        self._mgr.wait()

    def close(self) -> None:
        self._mgr.close()


@dataclasses.dataclass
class TrainResult:
    val_loss: float
    val_accuracy: float
    history: list[dict[str, float]]
    state: TrainState
    epochs_run: int


class Trainer:
    def __init__(
        self,
        data_cfg: DataCfg,
        model_cfg: ModelCfg,
        train_cfg: TrainCfg,
        mesh=None,
        run: Run | None = None,
        model=None,
        initial=None,
        on_epoch=None,
        tracer=None,
    ):
        """``model`` overrides the registry module (e.g. a
        :class:`ddw_tpu.train.transfer.TransferHead` trained on a cached-feature
        table); ``initial=(state, tx)`` supplies a pre-built TrainState +
        optimizer instead of ``init_state`` (the override pair the
        cached-feature path uses — the head starts from the full model's init).
        ``on_epoch(row)`` is called after each epoch's metrics/callbacks with
        the history row; returning True stops training, and exceptions
        propagate out of ``fit`` (how HPO pruners abort a trial —
        ``ddw_tpu.tune.pruner``)."""
        self.data_cfg = data_cfg
        self.model_cfg = model_cfg
        self.train_cfg = train_cfg
        if mesh is None:
            devices = jax.devices()
            if train_cfg.num_devices:
                devices = devices[: train_cfg.num_devices]
            # DCN-aware by default: multi-slice jobs get a slice-major data
            # axis with zero configuration (runtime.mesh.make_data_mesh).
            mesh = make_data_mesh(devices=devices)
        self.mesh = mesh
        self.run = run
        self.model = model if model is not None else build_model(model_cfg)
        self._initial = initial
        self._on_epoch = on_epoch
        # optional obs.Tracer: the span tree of one fit and the loaders'
        # producer spans, the host-side record (docs/observability.md lists
        # them; ``TrainCfg.trace_dir`` makes one when none is given)
        self.tracer = tracer

    # -- sizing ---------------------------------------------------------------
    @property
    def world_size(self) -> int:
        """Number of data-parallel workers (devices on the data axis) — the
        ``hvd.size()`` analog."""
        return int(self.mesh.shape[self.train_cfg.data_axis])

    def _loaders(self, train_table: Table, val_table: Table,
                 consumed_batches: int = 0, super_plan=None, tracer=None):
        # Elastic-aware topology: under an elastic gang the data-parallel
        # ranks live in the rendezvous (jax.distributed is per-process), and
        # after a shrink recovery the re-derived loaders re-partition the
        # same shard set at the N-1 world so every sample is covered exactly
        # once per epoch (ShardedLoader.shard_plan).
        cur_proc, n_proc = process_topology()
        per_host_batch = self.train_cfg.batch_size * self.world_size // n_proc
        sharding = batch_sharding(self.mesh, self.train_cfg.data_axis)
        train_loader = ShardedLoader(
            train_table,
            batch_size=per_host_batch,
            image_size=(self.data_cfg.img_height, self.data_cfg.img_width),
            cur_shard=cur_proc,
            shard_count=n_proc,
            num_epochs=None,  # infinite repeat: identical step counts (§2b.8)
            shuffle=True,
            seed=self.train_cfg.seed,
            shuffle_buffer=self.data_cfg.shuffle_buffer,
            workers=self.data_cfg.loader_workers,
            prefetch=self.data_cfg.prefetch,
            prefetch_to=sharding,
            # True resume: fast-forward the deterministic stream to exactly
            # where the interrupted run stopped consuming.
            skip_records=consumed_batches * per_host_batch,
            # Fused-dispatch mode: [k, B, ...] super-batches stacked on
            # device per the epoch's chain plan (chain_plan(spe, K)).
            super_batch=super_plan,
            tracer=tracer,
        )
        val_loader_factory = lambda: ShardedLoader(  # noqa: E731 — fresh pass per epoch
            val_table,
            batch_size=per_host_batch,
            image_size=(self.data_cfg.img_height, self.data_cfg.img_width),
            cur_shard=cur_proc,
            shard_count=n_proc,
            num_epochs=None,  # infinite repeat: floor-divided val_steps can exceed
                              # one pass when shards are small (reference :199-200)
            shuffle=False,
            workers=self.data_cfg.loader_workers,
            prefetch=self.data_cfg.prefetch,
            prefetch_to=sharding,
            tracer=tracer,
        )
        return train_loader, val_loader_factory

    # -- main loop ------------------------------------------------------------
    def fit(self, train_table: Table, val_table: Table, resume: bool = False) -> TrainResult:
        t_fit = time.monotonic()
        cfg = self.train_cfg
        world = self.world_size
        profiling = bool(cfg.trace_dir) and process_topology()[0] == 0
        tracer = self.tracer
        if profiling and tracer is None:
            # an operator's trace is the device profile AND the span tree
            tracer = Tracer(capacity=65536, process="train")
        # every boundary below is stamped once; the stamps feed the span tree
        # (a no-op lane without a tracer) and the telemetry hub alike
        sp = span_lane(tracer, "train", "train")
        setup_id = sp.open()

        if self._initial is not None:
            state, tx = self._initial
            if cfg.ema_decay and ema_params(state) is None:
                # the pre-built optimizer was not EMA-wrapped; silently
                # evaluating raw params while the user asked for EMA (or
                # crashing later with params=None) are both worse than this
                raise ValueError(
                    "train.ema_decay is set but the provided initial "
                    "optimizer state carries no EMA shadow — build the tx "
                    "with ddw_tpu.train.step.with_param_ema or drop the flag")
        else:
            rng = jax.random.PRNGKey(cfg.seed)
            state, tx = init_state(
                self.model, self.model_cfg, cfg,
                (self.data_cfg.img_height, self.data_cfg.img_width, self.data_cfg.channels),
                rng,
            )
            # init_state makes the optimizer and its state too
            sp.span("model_init", t_fit, time.monotonic(), setup_id)
        t0 = time.monotonic()
        sharded_state = cfg.zero or cfg.fsdp
        if sharded_state:
            if cfg.zero and cfg.fsdp:
                raise ValueError("train.zero and train.fsdp are mutually "
                                 "exclusive (fsdp already shards the "
                                 "optimizer state) — pick one")
            # zero/fsdp compose with async_checkpoint: the sharded manager
            # snapshots shards to host at the boundary and runs the
            # collective commit protocol on per-process background writers.
            from ddw_tpu.parallel.zero import (
                make_fsdp_train_chain,
                make_fsdp_train_step,
                make_zero_train_chain,
                make_zero_train_step,
            )

            make_sharded = (make_fsdp_train_step if cfg.fsdp
                            else make_zero_train_step)
            train_step = make_sharded(self.model, tx, self.mesh,
                                      cfg.data_axis,
                                      grad_accum_steps=cfg.grad_accum_steps)
            make_chain = (make_fsdp_train_chain if cfg.fsdp
                          else make_zero_train_chain)
        else:
            train_step = make_train_step(self.model, tx, self.mesh, cfg.data_axis,
                                         grad_accum_steps=cfg.grad_accum_steps)
            make_chain = make_train_chain
        if cfg.steps_per_dispatch < 1:
            raise ValueError(f"train.steps_per_dispatch must be >= 1, got "
                             f"{cfg.steps_per_dispatch}")
        # Fused K-step dispatch (steps_per_dispatch > 1): ONE compiled scan
        # program covers K optimizer updates fed by a loader-stacked
        # [k, B, ...] super-batch; built lazily below once steps_per_epoch
        # fixes the chain plan. K=1 keeps the per-step dispatch path.
        train_chain = (make_chain(self.model, tx, self.mesh, cfg.data_axis,
                                  grad_accum_steps=cfg.grad_accum_steps)
                       if cfg.steps_per_dispatch > 1 else None)
        eval_step = make_eval_step(self.model, self.mesh, cfg.data_axis)
        t1 = time.monotonic()
        sp.span("build_step", t0, t1, setup_id)

        if not cfg.checkpoint_dir:
            ckpt = None
        elif sharded_state:
            # sharded per-process format: saving must NOT all-gather the
            # ZeRO/FSDP-sharded leaves into one host (checkpoint/sharded.py)
            ckpt = _ZeroCheckpointAdapter(
                cfg.checkpoint_dir, self.mesh, cfg.data_axis, fsdp=cfg.fsdp,
                async_write=cfg.async_checkpoint,
                max_inflight=cfg.async_checkpoint_inflight)
        else:
            ckpt = CheckpointManager(
                cfg.checkpoint_dir, async_write=cfg.async_checkpoint,
                max_inflight=cfg.async_checkpoint_inflight)
        start_epoch = 0
        steps_per_epoch = max(1, train_table.num_records // (cfg.batch_size * world))
        val_steps = max(1, val_table.num_records // (cfg.batch_size * world))
        restored_meta = None
        if ckpt and resume:
            state, at_step = ckpt.restore(state)
            if at_step is not None:
                start_epoch = int(at_step) // steps_per_epoch
                restored_meta = ckpt.read_metadata(at_step)
            sp.span("restore", t1, time.monotonic(), setup_id)
        if sharded_state:
            # leaves onto their data-axis shards (no-op on a restored
            # already-sharded state)
            state = train_step.place_state(state)

        best = None
        if cfg.checkpoint_keep_best:
            if not ckpt:
                raise ValueError("checkpoint_keep_best needs a "
                                 "checkpoint_dir")
            from ddw_tpu.checkpoint.ckpt import BestCheckpointKeeper

            best = BestCheckpointKeeper(
                cfg.checkpoint_dir,
                (lambda d: _ZeroCheckpointAdapter(
                    d, self.mesh, cfg.data_axis, fsdp=cfg.fsdp, keep=1,
                    async_write=cfg.async_checkpoint))
                if sharded_state else
                (lambda d: CheckpointManager(
                    d, keep=1, async_write=cfg.async_checkpoint)))

        # warmup/cosine/plateau/early + counter restore, shared with the LM
        # trainer (train/schedule.py holds the ordering/resume rules)
        sched = ScheduleSuite.build(cfg, world, restored_meta)

        if self.run is not None:
            self.run.log_params({f"train.{k}": v for k, v in to_dict(cfg).items()})
            self.run.log_params({f"model.{k}": v for k, v in to_dict(self.model_cfg).items()})
            self.run.log_params({"world_size": world,
                                 "steps_per_epoch": steps_per_epoch,
                                 "global_batch": cfg.batch_size * world})

        monitor = None
        if (cfg.monitor_interval_s > 0 and self.run is not None
                and process_topology()[0] == 0):
            # Ganglia role (SURVEY §5): sys.* utilization series next to the
            # training curves.
            from ddw_tpu.utils.sysmon import SystemMonitor

            monitor = SystemMonitor(self.run, cfg.monitor_interval_s)

        # Chain plan: lengths covering one epoch exactly (K-chains + one
        # trailing partial chain). All-ones (K=1, or steps_per_epoch < 2)
        # keeps the per-step dispatch path end to end.
        plan = chain_plan(steps_per_epoch, cfg.steps_per_dispatch)
        chained = train_chain is not None and any(k > 1 for k in plan)

        with monitor if monitor is not None else contextlib.nullcontext():
            t0 = time.monotonic()
            train_loader, val_loader_factory = self._loaders(
                train_table, val_table,
                consumed_batches=start_epoch * steps_per_epoch,
                super_plan=plan if chained else None, tracer=tracer)
            train_iter = iter(train_loader)
            sp.span("build_loaders", t0, time.monotonic(), setup_id)
            step_rng = jax.random.PRNGKey(cfg.seed + 1)

            history: list[dict[str, float]] = []
            val_loss = val_acc = float("nan")
            epochs_run = 0
            tracing = False
            # telemetry plane: a Run wrapped by obs.telemetry.tee_run
            # exposes its hub — chain dispatch and checkpoint-write
            # latencies become windowed dist series (docs/observability.md)
            hub = (getattr(self.run, "telemetry_hub", None)
                   if self.run is not None else None)
            resumed = ckpt is not None and resume and start_epoch > 0
            state = sched.initial_state(state, start_epoch, resumed)
            # TrainCfg.trace_dir: the device profile of the first SETTLED
            # epoch (the one before it compiles), device lines only — the
            # host tracer at its default more than doubles an epoch — with
            # the tracer's ring written beside it
            profile_epoch = (min(start_epoch + 1, cfg.epochs - 1)
                             if profiling else -1)
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
                        if self.run is not None:
                            # The report links this param as the per-run
                            # profiler-trace artifact (Horovod-Timeline role).
                            self.run.log_params(
                                {"trace_dir": os.path.abspath(cfg.trace_dir)})
                    t0 = time.time()
                    losses, accs = [], []
                    step_i = 0
                    for k_chain in plan:
                        t_chain = time.monotonic()
                        chain_id = sp.open()
                        if setup_id is not None:
                            # set-up ends where the first chain starts
                            sp.span("fit_setup", t_fit, t_chain,
                                    span=setup_id)
                            setup_id = None
                        # Fault-injection hook (runtime.faults): free no-op
                        # unless DDW_FAULT targets this rank/step/generation.
                        # Under chained dispatch it (like the preemption check
                        # and the per-batch LR write below) fires at CHAIN
                        # boundaries — the host only regains control every
                        # k_chain steps (docs/performance.md).
                        maybe_fault("step",
                                    step=epoch * steps_per_epoch + step_i,
                                    ckpt_dir=cfg.checkpoint_dir or None)
                        # Elastic park point (no-op outside an elastic gang):
                        # a peer rank died and the gang re-formed — raise
                        # ElasticRestart HERE, at the chain boundary, so this
                        # surviving process re-enters fit(resume=True) from
                        # the latest durable checkpoint with its pid/programs
                        # intact (runtime/elastic.py). The finally block
                        # below joins the async ckpt writer on the way out.
                        maybe_elastic_restart(
                            step=epoch * steps_per_epoch + step_i)
                        if preemption_requested():
                            # Graceful preemption (SIGTERM): checkpoint the
                            # live state mid-epoch, then leave via Preempted —
                            # the gang worker converts it to EXIT_PREEMPTED so
                            # the supervisor restarts without burning the
                            # crash budget. The finally block below joins the
                            # async writer, making the save durable.
                            step_now = int(jax.device_get(state.step))
                            if ckpt:
                                t_ck = time.monotonic()
                                ckpt.save(state, step_now,
                                          metadata={"epoch": epoch,
                                                    "preempted": True,
                                                    "callbacks": sched.state_dicts()})
                                sp.span("ckpt_save", t_ck, time.monotonic(),
                                        epoch_id,
                                        args=sp.on and {"step": step_now})
                            raise Preempted(step_now)
                        # Per-batch LR: cosine everywhere, or the Goyal warmup
                        # ramp (Horovod warmup-callback granularity, reference
                        # :314-318); None past warmup in the plateau regime.
                        # set_lr is a dynamic-hyperparameter write — no
                        # recompilation.
                        lr_b = sched.lr_for_batch(epoch, step_i,
                                                  steps_per_epoch)
                        if lr_b is not None:
                            state = set_lr(state, lr_b)
                        t_wait = time.monotonic()
                        images, labels = next(train_iter)
                        t_disp = time.monotonic()
                        sp.span("data_wait", t_wait, t_disp, chain_id,
                                args=sp.on and {"step": step_i})
                        if chained:
                            # [k, B, ...] super-batch through the fused scan
                            # program; metrics come back as [k] per-step
                            # arrays — no per-step host work at all.
                            state, metrics = train_chain(state, images,
                                                         labels, step_rng)
                        else:
                            state, metrics = train_step(state, images, labels,
                                                        step_rng)
                        t_end = time.monotonic()
                        # enqueue plus back-pressure from the device queue
                        sp.span("dispatch", t_disp, t_end, chain_id,
                                args=sp.on and {"step": step_i, "k": k_chain})
                        losses.append(metrics["loss"])
                        accs.append(metrics["accuracy"])
                        # the chain boundary as the host sees it (device time
                        # for the chain lives in the jax.profiler trace, not
                        # here); its self time, less data_wait and dispatch,
                        # is the loop's own work
                        sp.span("train_chain", t_chain, t_end, epoch_id,
                                chain_id,
                                args=sp.on and {"epoch": epoch,
                                                "step": step_i, "k": k_chain,
                                                "chained": bool(chained)})
                        if hub is not None:
                            hub.observe("train.chain_ms",
                                        (t_end - t_chain) * 1e3)
                        step_i += k_chain
                    # ONE device reduction + fetch for the whole epoch
                    # (fetch_metrics_mean) instead of a device_get per scalar.
                    # The device drains here: validation starts on an idle
                    # chip.
                    t_f = time.monotonic()
                    train_loss = fetch_metrics_mean(losses)
                    train_acc = fetch_metrics_mean(accs)
                    epoch_s = time.time() - t0
                    t_val = time.monotonic()
                    sp.span("train_fetch", t_f, t_val, epoch_id)

                    vlosses, vaccs = [], []
                    val_id = sp.open()
                    # the first wait holds the building of this epoch's
                    # validation loader and the start of its producer
                    viter = iter(val_loader_factory())
                    # ZeRO/FSDP: eval reads only params/batch_stats — pass the
                    # state without the sharded moments or the eval jit would
                    # all-gather them to match its replicated in_spec (FSDP
                    # params do get gathered — eval wants full weights)
                    eval_state = (state.replace(opt_state=()) if sharded_state
                                  else state)
                    if cfg.ema_decay:
                        # evaluate the Polyak shadow (what serving should ship)
                        eval_state = eval_state.replace(
                            params=ema_params(state), opt_state=())
                    t0v = t_val
                    for i in range(val_steps):
                        images, labels = next(viter)
                        t1v = time.monotonic()
                        sp.span("val_data_wait", t0v, t1v, val_id,
                                args=sp.on and {"i": i, "first": i == 0})
                        m = eval_step(eval_state, images, labels)
                        vlosses.append(m["loss"])
                        vaccs.append(m["accuracy"])
                        t0v = time.monotonic()
                        sp.span("val_dispatch", t1v, t0v, val_id,
                                args=sp.on and {"i": i})
                    sp.span("validation", t_val, t0v, epoch_id, val_id,
                            args=sp.on and {"steps": val_steps})
                    val_loss = fetch_metrics_mean(vlosses)
                    val_acc = fetch_metrics_mean(vaccs)

                    lr = get_lr(state)
                    t_rep = time.monotonic()
                    sp.span("epoch_fetch", t0v, t_rep, epoch_id)
                    if tracing:
                        # after the barrier, so the profile holds the epoch's
                        # validation and every device operation of it
                        jax.profiler.stop_trace()
                        tracing = False
                    row = {
                        "epoch": epoch, "loss": train_loss, "accuracy": train_acc,
                        "val_loss": val_loss, "val_accuracy": val_acc, "lr": lr,
                        "epoch_seconds": epoch_s,
                        "images_per_sec": steps_per_epoch * cfg.batch_size * world / epoch_s,
                    }
                    history.append(row)
                    epochs_run = epoch + 1
                    if self.run is not None:
                        self.run.log_metrics(
                            {k: v for k, v in row.items() if k != "epoch"}, step=epoch)
                    t_cb = time.monotonic()
                    sp.span("epoch_report", t_rep, t_cb, epoch_id)

                    end_id = sp.open()
                    if cfg.debug_cross_host_checks:
                        # SPMD consistency sanitizer (SURVEY §5): params must be identical
                        # across hosts; checksum computed locally, compared via tracker logs.
                        self.run and self.run.log_metric("params_checksum", params_checksum(state), epoch)

                    # LR-plateau AFTER metrics are world-consistent (ordering contract,
                    # reference :310-313 — trivially satisfied: metrics are pmean-ed in-step)
                    state, stop = sched.epoch_end(state, val_loss, epoch)
                    if self._on_epoch is not None and self._on_epoch(row):
                        stop = True

                    # Checkpoint AFTER the callbacks consumed this epoch's metrics,
                    # so the saved counters (and any plateau LR cut) are exactly the
                    # state the next epoch starts from — resume = continuation.
                    if ckpt and ((epoch + 1) % cfg.checkpoint_every_epochs == 0):
                        t_ck = time.monotonic()
                        step_now = int(jax.device_get(state.step))
                        ckpt.save(state, step_now,
                                  metadata={"epoch": epoch, "val_loss": val_loss,
                                            "val_accuracy": val_acc,
                                            "callbacks": sched.state_dicts()})
                        t1 = time.monotonic()
                        sp.span("ckpt_save", t_ck, t1, end_id,
                                args=sp.on and {"step": step_now})
                        if hub is not None:
                            hub.observe("train.ckpt_write_ms",
                                        (t1 - t_ck) * 1e3)
                    if best is not None:
                        best.maybe_save(state, int(jax.device_get(state.step)),
                                        row, {"epoch": epoch})
                    t1 = time.monotonic()
                    sp.span("epoch_end", t_cb, t1, epoch_id, end_id)
                    sp.span("epoch", t_epoch, t1, span=epoch_id,
                            args=sp.on and {"epoch": epoch,
                                            "steps": steps_per_epoch})
                    if epoch == profile_epoch:
                        # the spans of everything so far, the profiled epoch
                        # whole, in the form Perfetto loads beside the profile
                        with open(os.path.join(cfg.trace_dir,
                                               "train_spans.trace.json"),
                                  "w") as f:
                            json.dump(chrome_trace(tracer.drain()), f)
                    if stop:
                        break

            finally:
                # Always runs — including the documented abort path where
                # on_epoch / a pruner raises out of fit (examples 04/05):
                # the async ckpt writer thread is joined and released, and
                # any in-flight background write error surfaces here rather
                # than being dropped; a dangling profiler trace is closed.
                try:
                    if tracing:
                        jax.profiler.stop_trace()
                finally:
                    # unconditional even if stop_trace raises: the writer
                    # thread must be joined either way
                    if ckpt is not None:
                        ckpt.close()
                    if best is not None:
                        best.close()
            return TrainResult(val_loss, val_acc, history, state, epochs_run)
