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
import time

import jax

from ddw_tpu.data.loader import ShardedLoader
from ddw_tpu.data.store import Table
from ddw_tpu.models.registry import build_model
from ddw_tpu.runtime.elastic import process_topology
from ddw_tpu.runtime.mesh import make_data_mesh
from ddw_tpu.tracking.tracker import Run
from ddw_tpu.train import loop
from ddw_tpu.train.loop import TrainResult
from ddw_tpu.train.schedule import ScheduleSuite
from ddw_tpu.train.step import (
    batch_sharding,
    chain_plan,
    ema_params,
    init_state,
    make_eval_step,
    make_train_chain,
    make_train_step,
    params_checksum,
)
from ddw_tpu.utils.config import DataCfg, ModelCfg, TrainCfg


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

    def _loaders(self, train_table: Table, val_table: Table, val_steps: int,
                 consumed_batches: int = 0, super_plan=None, tracer=None):
        """``(train_loader, val_loader)``: the one infinite training stream,
        and the validation pass, which the loop opens anew each epoch."""
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

        # a pass of val_steps batches from the table's start, opened anew an
        # epoch (loop.run_epochs): the same records in the same order
        val_loader = ShardedLoader(
            val_table,
            batch_size=per_host_batch,
            image_size=(self.data_cfg.img_height, self.data_cfg.img_width),
            cur_shard=cur_proc,
            shard_count=n_proc,
            num_epochs=None,  # infinite repeat: floor-divided val_steps can exceed
                              # one pass when shards are small (reference :199-200)
            num_batches=val_steps,
            shuffle=False,
            workers=self.data_cfg.loader_workers,
            prefetch=self.data_cfg.prefetch,
            prefetch_to=sharding,
            tracer=tracer,
        )
        return train_loader, val_loader

    # -- main loop ------------------------------------------------------------
    def fit(self, train_table: Table, val_table: Table, resume: bool = False) -> TrainResult:
        t_fit = time.monotonic()
        cfg = self.train_cfg
        world = self.world_size
        tracer, sp, setup_id = loop.open_fit(cfg, self.tracer)

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
        steps_per_epoch = max(1, train_table.num_records // (cfg.batch_size * world))
        val_steps = max(1, val_table.num_records // (cfg.batch_size * world))
        # Chain plan: lengths covering one epoch exactly (K-chains + one
        # trailing partial chain; refuses steps_per_dispatch < 1). All-ones
        # (K=1, or steps_per_epoch < 2) keeps the per-step dispatch path.
        plan = chain_plan(steps_per_epoch, cfg.steps_per_dispatch)
        chained = any(k > 1 for k in plan)
        # Fused K-step dispatch: ONE compiled scan program covers K optimizer
        # updates fed by a loader-stacked [k, B, ...] super-batch.
        run_step = (make_chain(self.model, tx, self.mesh, cfg.data_axis,
                               grad_accum_steps=cfg.grad_accum_steps)
                    if chained else train_step)
        eval_step = make_eval_step(self.model, self.mesh, cfg.data_axis)
        t1 = time.monotonic()
        sp.span("build_step", t0, t1, setup_id)

        ckpt, best = loop.open_checkpoints(cfg, self.mesh, cfg.data_axis)
        start_epoch, restored_meta = 0, None
        if ckpt and resume:
            state, start_epoch, restored_meta = loop.restore(
                ckpt, state, steps_per_epoch)
            sp.span("restore", t1, time.monotonic(), setup_id)
        state = loop.place_state(run_step, state, sp, setup_id)

        # warmup/cosine/plateau/early + counter restore, shared with the LM
        # trainer (train/schedule.py holds the ordering/resume rules)
        sched = ScheduleSuite.build(cfg, world, restored_meta)
        loop.log_fit_params(self.run, {"world_size": world,
                                       "steps_per_epoch": steps_per_epoch,
                                       "global_batch": cfg.batch_size * world},
                            train=cfg, model=self.model_cfg)

        monitor = None
        if (cfg.monitor_interval_s > 0 and self.run is not None
                and process_topology()[0] == 0):
            # Ganglia role (SURVEY §5): sys.* utilization series next to the
            # training curves.
            from ddw_tpu.utils.sysmon import SystemMonitor

            monitor = SystemMonitor(self.run, cfg.monitor_interval_s)

        with monitor if monitor is not None else contextlib.nullcontext():
            t0 = time.monotonic()
            train_loader, val_loader = self._loaders(
                train_table, val_table, val_steps,
                consumed_batches=start_epoch * steps_per_epoch,
                super_plan=plan if chained else None, tracer=tracer)
            train_iter = iter(train_loader)
            sp.span("build_loaders", t0, time.monotonic(), setup_id)
            step_rng = jax.random.PRNGKey(cfg.seed + 1)

            def on_epoch(row, state):
                if cfg.debug_cross_host_checks and self.run:
                    # SPMD consistency sanitizer (SURVEY §5): params must be
                    # identical across hosts; checksum computed locally,
                    # compared via tracker logs.
                    self.run.log_metric("params_checksum",
                                        params_checksum(state), row["epoch"])
                return self._on_epoch is not None and self._on_epoch(row)

            items = steps_per_epoch * cfg.batch_size * world
            return loop.run_epochs(
                cfg=cfg, state=state, sched=sched, plan=plan,
                start_epoch=start_epoch,
                # the one infinite stream: an item a chain, epoch after epoch
                train_batches=lambda epoch: train_iter,
                val_batches=val_loader,
                step_args=lambda batch, host_step: (*batch, step_rng),
                run_step=run_step, eval_step=eval_step, ckpt=ckpt, best=best,
                run=self.run, tracer=tracer, setup_id=setup_id, t_fit=t_fit,
                # epoch_seconds is the training part of the epoch
                timed_row=lambda s: {"epoch_seconds": s,
                                     "images_per_sec": items / s},
                on_epoch=on_epoch)
