"""LM trainer: the Trainer amenities for the long-context model family.

The vision :class:`ddw_tpu.train.trainer.Trainer` mirrors the reference's
``train_and_evaluate`` contracts; the LM family (beyond parity — the
reference has no language model, SURVEY.md §5 "Long-context ... Absent")
previously trained through hand-rolled loops (example 07). This wraps the
same loop machinery around :mod:`ddw_tpu.train.lm_step`:

- DP×SP mesh construction (``seq_devices`` splits the sequence axis; the
  model binds the ring-attention axis automatically),
- the shared callback suite — per-batch Goyal warmup, plateau or cosine LR,
  early stopping — driven through the same dynamic-LR optimizer state,
- epoch checkpoints with callback-counter metadata and exact resume
  (deterministic per-epoch shuffle keyed by ``seed + epoch``: an
  epoch-boundary resume replays the uninterrupted stream),
- tracker logging (params once, metrics per epoch).

Data model: one token array ``[num_seqs, seq_len + 1]`` (next-token pairs
are carved per batch); a held-out validation split is taken up front with a
seeded permutation, mirroring the reference's seed-42 split discipline.
"""

from __future__ import annotations

import dataclasses
import time
import warnings

import jax
import numpy as np

from ddw_tpu.checkpoint.ckpt import CheckpointManager
from ddw_tpu.models.lm import build_lm
from ddw_tpu.obs.trace import span_lane
from ddw_tpu.runtime.elastic import maybe_elastic_restart, process_topology
from ddw_tpu.runtime.faults import Preempted, maybe_fault, preemption_requested
from ddw_tpu.runtime.mesh import (DATA_AXIS, PIPE_AXIS, SEQ_AXIS, MeshSpec,
                                  make_data_mesh, make_mesh)
from ddw_tpu.train.lm_step import (
    init_lm_state,
    make_lm_eval_step,
    make_lm_train_chain,
    make_lm_train_step,
)
from ddw_tpu.train.schedule import ScheduleSuite
from ddw_tpu.train.step import (TrainState, chain_plan, ema_params,
                                fetch_metrics_mean, get_lr, make_optimizer,
                                set_lr)
from ddw_tpu.utils.config import LMCfg, TrainCfg, to_dict


@dataclasses.dataclass
class LMTrainResult:
    val_loss: float
    val_accuracy: float
    history: list[dict[str, float]]
    state: TrainState
    epochs_run: int


class LMTrainer:
    """``fit(tokens)`` for :class:`ddw_tpu.models.lm.TransformerLM`."""

    def __init__(self, lm_cfg: LMCfg, train_cfg: TrainCfg,
                 mesh=None, seq_devices: int = 1, run=None, tracer=None):
        self.lm_cfg, self.train_cfg, self.run = lm_cfg, train_cfg, run
        # optional obs.Tracer: the span tree of one fit, and the loaders'
        # producer spans (docs/observability.md lists them)
        self.tracer = tracer
        self.pp = train_cfg.pipeline_stages > 0
        self.sharded = train_cfg.zero or train_cfg.fsdp
        if train_cfg.ema_decay and getattr(lm_cfg, "lora_rank", 0):
            # fail at construction like every other invalid combination:
            # LoRA wraps inside init_lm_state's _maybe_lora_tx, which would
            # put the mask outside the EMA shadow
            raise ValueError("train.ema_decay with lm.lora_rank is not "
                             "supported: the LoRA mask would wrap outside "
                             "the EMA shadow — drop one")
        if self.sharded:
            flag = "train.fsdp" if train_cfg.fsdp else "train.zero"
            if train_cfg.zero and train_cfg.fsdp:
                raise ValueError("train.zero and train.fsdp are mutually "
                                 "exclusive (fsdp already shards the "
                                 "optimizer state) — pick one")
            # zero/fsdp compose with async_checkpoint: the sharded manager
            # snapshots shards to host at the boundary and runs the
            # collective commit protocol on per-process background writers.
            if self.pp:
                raise ValueError(f"{flag} does not compose with "
                                 f"pipeline_stages — the pipeline step "
                                 f"already shards stage params over 'pipe'")
            if seq_devices != 1:
                raise ValueError(f"{flag} uses the GSPMD DP step (no "
                                 f"sequence axis) — seq_devices must be 1")
            if lm_cfg.num_experts:
                raise ValueError(
                    f"{flag} does not support MoE models: the GSPMD step's "
                    f"forward discards the sown Switch aux loss, which would "
                    f"silently train an unbalanced router — use the plain "
                    f"DP/EP step (no zero/fsdp) for MoE")
        if train_cfg.steps_per_dispatch < 1:
            raise ValueError(f"train.steps_per_dispatch must be >= 1, got "
                             f"{train_cfg.steps_per_dispatch}")
        if self.pp:
            if train_cfg.steps_per_dispatch > 1:
                raise ValueError("steps_per_dispatch does not compose with "
                                 "pipeline_stages — the pipeline step already "
                                 "fuses its microbatch schedule into one "
                                 "dispatch; raise pipeline_microbatches "
                                 "instead")
            if seq_devices != 1:
                raise ValueError("pipeline_stages does not compose with "
                                 "seq_devices — the pipeline step shards "
                                 "depth, not sequence (use one or the other)")
            if lm_cfg.dropout:
                raise ValueError("pipeline training requires lm.dropout == 0 "
                                 "(the pipeline step is deterministic)")
            if train_cfg.grad_accum_steps > 1:
                raise ValueError("pipeline_stages does not compose with "
                                 "grad_accum_steps — microbatching IS the "
                                 "pipeline's accumulation; raise "
                                 "pipeline_microbatches instead")
        if mesh is None:
            devices = jax.devices()
            if train_cfg.num_devices:
                devices = devices[: train_cfg.num_devices]
            n = len(devices)
            if seq_devices < 1:
                raise ValueError(f"seq_devices must be >= 1, got "
                                 f"{seq_devices}")
            if n % seq_devices:
                raise ValueError(f"seq_devices {seq_devices} must divide "
                                 f"device count {n}")
            if self.pp:
                stages = train_cfg.pipeline_stages
                if n % stages:
                    raise ValueError(f"pipeline_stages {stages} must divide "
                                     f"device count {n}")
                mesh = make_mesh(MeshSpec(((DATA_AXIS, n // stages),
                                           (PIPE_AXIS, stages))),
                                 devices=devices)
            elif seq_devices == 1:
                ep = lm_cfg.num_experts and not (self.pp or self.sharded)
                if ep:
                    # EP all-to-alls ride the data axis PER LAYER — the
                    # slice-major hybrid layout would put them on the DCN
                    # (exactly what HybridMeshSpec refuses for model/seq).
                    # Keep the flat ICI-optimized mesh for MoE routing.
                    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)),
                                     devices=devices)
                else:
                    # DCN-aware by default (runtime.mesh.make_data_mesh)
                    mesh = make_data_mesh(devices=devices)
            else:
                dp = n // seq_devices
                mesh = make_mesh(MeshSpec(((DATA_AXIS, dp),
                                           (SEQ_AXIS, seq_devices))),
                                 devices=devices)
        if self.pp:
            # A user-supplied mesh must actually realize the configured
            # layout — a silent stage-count mismatch or a missing data axis
            # would otherwise surface as a wrong parallelism layout or a
            # bare KeyError deep inside fit.
            if mesh.shape.get(PIPE_AXIS) != train_cfg.pipeline_stages:
                raise ValueError(
                    f"pipeline_stages={train_cfg.pipeline_stages} but the "
                    f"mesh is {dict(mesh.shape)} — its '{PIPE_AXIS}' axis "
                    f"must exist with exactly that size")
            if DATA_AXIS not in mesh.shape:
                raise ValueError(
                    f"the pipeline trainer batches over '{DATA_AXIS}'; give "
                    f"the mesh a (possibly size-1) '{DATA_AXIS}' axis: "
                    f"{dict(mesh.shape)}")
        self.mesh = mesh
        self.seq_axis = SEQ_AXIS if SEQ_AXIS in mesh.shape else None
        # Under PP and ZeRO/FSDP (GSPMD steps with no named axis inside the
        # program), MoE experts stay dense/local; otherwise EP routes over
        # the data axis.
        self.model = build_lm(lm_cfg, seq_axis=self.seq_axis,
                              expert_axis=(DATA_AXIS if lm_cfg.num_experts
                                           and not (self.pp or self.sharded)
                                           else None))

    # ------------------------------------------------------------------
    def fit(self, tokens: np.ndarray, val_fraction: float = 0.1,
            resume: bool = False) -> LMTrainResult:
        """Train from an in-memory token corpus ``[num_seqs, seq_len+1]``."""
        t_fit = time.monotonic()
        cfg = self.train_cfg
        dp = self.mesh.shape[DATA_AXIS]
        sp = self.mesh.shape.get(SEQ_AXIS, 1)

        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 2 or tokens.shape[1] < 2:
            raise ValueError(f"tokens must be [num_seqs, seq_len+1], got "
                             f"{tokens.shape}")
        seq_len = tokens.shape[1] - 1
        if seq_len % sp:
            raise ValueError(f"seq_len {seq_len} not divisible by "
                             f"seq_devices {sp}")

        # Seeded split (the seed-42 discipline, reference 01_data_prep.py).
        perm = np.random.RandomState(cfg.seed).permutation(len(tokens))
        n_val = max(1, int(len(tokens) * val_fraction))
        val, train = tokens[perm[:n_val]], tokens[perm[n_val:]]

        global_batch = cfg.batch_size * dp
        steps_per_epoch = max(1, len(train) // global_batch)
        val_steps = max(1, len(val) // global_batch)
        if len(train) < global_batch:
            raise ValueError(f"{len(train)} train sequences < global batch "
                             f"{global_batch}")

        def make_providers(start_epoch, step, plan, chained):
            def train_batches(epoch):
                order = np.random.RandomState(cfg.seed + 1 + epoch
                                              ).permutation(len(train))
                i = 0
                for k in plan:
                    idx = order[i * global_batch:(i + k) * global_batch]
                    i += k
                    b = train[idx]
                    if chained:
                        # [k, global_batch, S+1] super-batch: the SAME k
                        # consecutive batches the per-step path would draw,
                        # reshaped for the fused scan program.
                        b = b.reshape(k, global_batch, -1)
                        yield b[:, :, :-1], b[:, :, 1:]
                    else:
                        yield b[:, :-1], b[:, 1:]

            def val_batches():
                for i in range(val_steps):
                    # index modulo the split: every eval batch is exactly
                    # global_batch (shard_map divisibility) even for tiny
                    # validation sets
                    idx = np.arange(i * global_batch,
                                    (i + 1) * global_batch) % len(val)
                    vb = val[idx]
                    yield vb[:, :-1], vb[:, 1:]

            return train_batches, val_batches

        return self._run(seq_len, steps_per_epoch, val_steps, global_batch,
                         make_providers, resume, t_fit)

    def fit_tables(self, train_table, val_table,
                   resume: bool = False) -> LMTrainResult:
        """Train from materialized token tables (``prep.write_token_table``)
        — the LM family through the same store -> sharded-loader path the
        vision families use: shard-selected reads, seeded shuffle, infinite
        repeat, exact ``skip_records`` resume of the consumed stream."""
        from ddw_tpu.data.loader import ShardedLoader

        t_fit = time.monotonic()
        cfg = self.train_cfg
        dp = self.mesh.shape[DATA_AXIS]
        sp = self.mesh.shape.get(SEQ_AXIS, 1)

        for tbl, role in ((train_table, "train"), (val_table, "val")):
            if tbl.meta.get("encoding") != "tokens_i32":
                raise ValueError(
                    f"{role} table encoding "
                    f"{tbl.meta.get('encoding')!r} != 'tokens_i32' — "
                    f"materialize with prep.write_token_table")
        spo = train_table.meta["seq_plus_one"]
        if val_table.meta["seq_plus_one"] != spo:
            raise ValueError("train/val token tables disagree on sequence "
                             "length")
        seq_len = spo - 1
        if seq_len % sp:
            raise ValueError(f"seq_len {seq_len} not divisible by "
                             f"seq_devices {sp}")

        global_batch = cfg.batch_size * dp
        steps_per_epoch = train_table.num_records // global_batch
        if steps_per_epoch < 1:
            raise ValueError(f"{train_table.num_records} train sequences < "
                             f"global batch {global_batch}")
        val_steps = val_table.num_records // global_batch
        if val_steps < 1:
            raise ValueError(
                f"{val_table.num_records} val sequences < global batch "
                f"{global_batch} — the eval pass needs at least one full "
                f"batch (static shapes)")

        # Multi-process: each host reads a disjoint shard subset and a
        # per-host slice of the batch; the loader assembles global arrays
        # (make_array_from_process_local_data) via prefetch_to — the same
        # wiring as the vision Trainer. PP lacks a batch sharding to
        # assemble onto; refuse rather than silently duplicate data.
        cur_proc, n_proc = process_topology()
        if n_proc > 1 and self.pp:
            raise ValueError("fit_tables under multi-process pipeline "
                             "parallelism is not supported — run PP "
                             "single-process or use fit(tokens)")
        if global_batch % n_proc:
            raise ValueError(f"global batch {global_batch} not divisible by "
                             f"{n_proc} processes")
        host_batch = global_batch // n_proc

        def make_providers(start_epoch, step, plan, chained):
            prefetch_to = getattr(step, "batch_sharding", None)
            if n_proc > 1 and prefetch_to is None:
                raise ValueError("multi-process fit_tables needs a step "
                                 "with a batch sharding to assemble global "
                                 "arrays")
            if chained and prefetch_to is None:
                raise ValueError("steps_per_dispatch > 1 under fit_tables "
                                 "needs a step with a batch sharding — the "
                                 "loader stacks super-batches on device")
            shard_kw = dict(cur_shard=cur_proc, shard_count=n_proc,
                            prefetch_to=prefetch_to, tracer=self.tracer)
            train_iter = iter(ShardedLoader(
                train_table, batch_size=host_batch, num_epochs=None,
                shuffle=True, seed=cfg.seed + 1,
                skip_records=start_epoch * steps_per_epoch * host_batch,
                # chained: the loader stacks [k, B, S] token super-batches on
                # its prefetch thread per the epoch plan (same record stream,
                # same H2D bytes — only dispatch granularity changes)
                super_batch=plan if chained else None,
                **shard_kw))

            def train_batches(epoch):
                # one item per chain (len(plan) == steps_per_epoch when K=1)
                for _ in range(len(plan)):
                    yield next(train_iter)

            def val_batches():
                # fresh unshuffled single pass per epoch: every eval sees
                # the SAME leading val_steps full batches (no window drift
                # across epochs or resumes)
                loader = ShardedLoader(val_table, batch_size=host_batch,
                                       num_epochs=1, shuffle=False,
                                       **shard_kw)
                for i, batch in enumerate(loader):
                    if i >= val_steps:
                        break
                    yield batch

            return train_batches, val_batches

        return self._run(seq_len, steps_per_epoch, val_steps, global_batch,
                         make_providers, resume, t_fit)

    def _run(self, seq_len, steps_per_epoch, val_steps, global_batch,
             make_providers, resume, t_fit) -> LMTrainResult:
        cfg = self.train_cfg
        mesh = self.mesh
        dp = mesh.shape[DATA_AXIS]
        # every boundary below is stamped once; the stamps feed the span tree
        # (a no-op lane without a tracer) and the telemetry hub alike
        sp = span_lane(self.tracer, "train", "train")
        setup_id = sp.open()

        t0 = time.monotonic()
        tx = make_optimizer(cfg)
        if cfg.ema_decay:
            from ddw_tpu.train.step import with_param_ema

            # Outermost wrap (mirrors vision init_state): the shadow tracks
            # the final post-mask updates (LoRA+EMA refused in __init__).
            tx = with_param_ema(tx, cfg.ema_decay)
        # Fused K-step dispatch: chain plan covering one epoch exactly
        # (PP refused in __init__; all-ones plan keeps the per-step path).
        plan = chain_plan(steps_per_epoch, cfg.steps_per_dispatch)
        chained = cfg.steps_per_dispatch > 1 and any(k > 1 for k in plan)
        rng = jax.random.PRNGKey(cfg.seed)
        t1 = time.monotonic()
        sp.span("optimizer_init", t0, t1, setup_id)
        if self.pp:
            from ddw_tpu.parallel.pipeline import (init_pp_state,
                                                   make_pp_lm_train_step)

            vstages = (cfg.pipeline_virtual_stages
                       if cfg.pipeline_schedule == "interleaved" else 1)
            state = init_pp_state(self.model, tx, mesh, rng,
                                  virtual_stages=vstages)
            t2 = time.monotonic()
            step = make_pp_lm_train_step(
                self.model, tx, mesh, data_axis=DATA_AXIS,
                num_microbatches=cfg.pipeline_microbatches,
                donate=True, schedule=cfg.pipeline_schedule,
                virtual_stages=vstages)
            eval_step = step.eval_step
        elif self.sharded:
            from ddw_tpu.parallel.zero import (make_fsdp_train_chain,
                                               make_fsdp_train_step,
                                               make_zero_train_chain,
                                               make_zero_train_step)

            state = init_lm_state(self.model, tx, rng,
                                  seq_len=min(8, seq_len))
            t2 = time.monotonic()
            make_sharded = (make_fsdp_train_step if cfg.fsdp
                            else make_zero_train_step)
            # DATA_AXIS, not cfg.data_axis: LMTrainer builds (and validates)
            # its meshes with the constant throughout.
            step = make_sharded(self.model, tx, mesh, DATA_AXIS,
                                grad_accum_steps=cfg.grad_accum_steps)
            if chained:
                make_sharded_chain = (make_fsdp_train_chain if cfg.fsdp
                                      else make_zero_train_chain)
                chain = make_sharded_chain(
                    self.model, tx, mesh, DATA_AXIS,
                    grad_accum_steps=cfg.grad_accum_steps)
            # Eval reads the sharded params through the shard_map eval step's
            # replicated in-spec: GSPMD gathers per eval call (same trade the
            # vision Trainer makes).
            eval_step = make_lm_eval_step(self.model, mesh,
                                          seq_axis=self.seq_axis)
        else:
            state = init_lm_state(self.model, tx, rng,
                                  seq_len=min(8, seq_len))
            t2 = time.monotonic()
            step = make_lm_train_step(self.model, tx, mesh,
                                      seq_axis=self.seq_axis,
                                      grad_accum_steps=cfg.grad_accum_steps)
            if chained:
                chain = make_lm_train_chain(
                    self.model, tx, mesh, seq_axis=self.seq_axis,
                    grad_accum_steps=cfg.grad_accum_steps)
            eval_step = make_lm_eval_step(self.model, mesh,
                                          seq_axis=self.seq_axis)
        t3 = time.monotonic()
        sp.span("model_init", t1, t2, setup_id)
        sp.span("build_step", t2, t3, setup_id)

        if not cfg.checkpoint_dir:
            ckpt = None
        elif self.sharded:
            # per-process sharded format: saving must NOT all-gather the
            # ZeRO/FSDP leaves into one host
            from ddw_tpu.train.trainer import _ZeroCheckpointAdapter

            ckpt = _ZeroCheckpointAdapter(
                cfg.checkpoint_dir, mesh, DATA_AXIS, fsdp=cfg.fsdp,
                async_write=cfg.async_checkpoint,
                max_inflight=cfg.async_checkpoint_inflight)
        else:
            ckpt = CheckpointManager(
                cfg.checkpoint_dir, async_write=cfg.async_checkpoint,
                max_inflight=cfg.async_checkpoint_inflight)
        start_epoch = 0
        restored_meta = None
        if ckpt and resume:
            state, at_step = ckpt.restore(state)
            if at_step is not None:
                start_epoch = int(at_step) // steps_per_epoch
                restored_meta = ckpt.read_metadata(at_step)
            sp.span("restore", t3, time.monotonic(), setup_id)

        if ckpt and resume and start_epoch > 0 and start_epoch >= cfg.epochs:
            # The restored checkpoint already covers every requested epoch —
            # the loop below would not run and the result would silently be
            # NaN. Surface the checkpoint's own last metrics so callers
            # gating on val_loss see the real numbers.
            saved = (restored_meta or {}).get("metrics")
            ckpt.close()
            if saved is None:
                raise ValueError(
                    f"resume=True restored a checkpoint at epoch "
                    f"{start_epoch} >= cfg.epochs={cfg.epochs}, and it "
                    f"predates metric metadata; raise cfg.epochs above "
                    f"{start_epoch} to continue training, or retrain")
            warnings.warn(
                f"resume=True restored a checkpoint at epoch {start_epoch} "
                f">= cfg.epochs={cfg.epochs}; the run is already complete — "
                f"returning the checkpointed metrics, no training performed")
            if self.pp or self.sharded:
                # Same placement contract as every normal completion:
                # callers that keep training or serving from result.state
                # must not see placement depend on which path returned.
                state = step.place_state(state)
            return LMTrainResult(val_loss=saved["val_loss"],
                                 val_accuracy=saved["val_accuracy"],
                                 history=[saved], state=state,
                                 epochs_run=start_epoch)

        if self.pp or self.sharded:
            # Placement AFTER restore: the checkpoint template is the
            # unplaced pytree; placing shards stage leaves over 'pipe' (PP)
            # or params/moments over the data axis (ZeRO/FSDP) — a no-op on
            # a restored already-sharded state.
            state = step.place_state(state)

        best = None
        if cfg.checkpoint_keep_best:
            if not ckpt:
                raise ValueError("checkpoint_keep_best needs a "
                                 "checkpoint_dir")
            from ddw_tpu.checkpoint.ckpt import BestCheckpointKeeper
            from ddw_tpu.train.trainer import _ZeroCheckpointAdapter

            best = BestCheckpointKeeper(
                cfg.checkpoint_dir,
                (lambda d: _ZeroCheckpointAdapter(
                    d, mesh, DATA_AXIS, fsdp=cfg.fsdp, keep=1,
                    async_write=cfg.async_checkpoint))
                if self.sharded else
                (lambda d: CheckpointManager(
                    d, keep=1, async_write=cfg.async_checkpoint)))

        sched = ScheduleSuite.build(cfg, dp, restored_meta)

        if self.run is not None:
            self.run.log_params(
                {f"train.{k}": v for k, v in to_dict(cfg).items()})
            self.run.log_params(
                {f"lm.{k}": v for k, v in to_dict(self.lm_cfg).items()})
            self.run.log_params({"mesh": dict(mesh.shape),
                                 "steps_per_epoch": steps_per_epoch,
                                 "global_batch": global_batch})

        t0 = time.monotonic()
        train_batches, val_batches = make_providers(
            start_epoch, chain if chained else step, plan, chained)
        sp.span("build_loaders", t0, time.monotonic(), setup_id)

        history: list[dict[str, float]] = []
        step_rng = jax.random.PRNGKey(cfg.seed + 1)
        epochs_run = start_epoch
        # telemetry plane: a Run wrapped by obs.telemetry.tee_run exposes
        # its hub — chain dispatch and checkpoint-write latencies become
        # windowed dist series beside the serving fleet's (same ladder)
        hub = (getattr(self.run, "telemetry_hub", None)
               if self.run is not None else None)
        resumed = ckpt is not None and resume and start_epoch > 0
        state = sched.initial_state(state, start_epoch, resumed)
        # Host-side step counter: folding the device counter into the rng
        # would force a blocking device_get every step (serializing async
        # dispatch); the host knows it exactly.
        host_step = int(jax.device_get(state.step))
        try:
            for epoch in range(start_epoch, cfg.epochs):
                t_epoch = time.monotonic()
                epoch_id = sp.open()
                tlosses, taccs = [], []
                batch_it = train_batches(epoch)
                step_i = 0
                for k_chain in plan:
                    t_chain = time.monotonic()
                    chain_id = sp.open()
                    if setup_id is not None:
                        # set-up ends where the first chain starts
                        sp.span("fit_setup", t_fit, t_chain, span=setup_id)
                        setup_id = None
                    inputs, targets = next(batch_it)
                    t_data = time.monotonic()
                    sp.span("data_wait", t_chain, t_data, chain_id,
                            args=sp.on and {"step": host_step})
                    # Fault-injection hook (runtime.faults): free no-op
                    # unless DDW_FAULT targets this rank/step/generation.
                    # Under chained dispatch the hook (and the preemption
                    # check / per-batch LR write) fires at CHAIN boundaries —
                    # the host only regains control every k_chain steps.
                    maybe_fault("step", step=host_step,
                                ckpt_dir=cfg.checkpoint_dir or None)
                    # Elastic park point (no-op outside an elastic gang): a
                    # dead peer re-forms the gang — leave via ElasticRestart
                    # at the chain boundary and re-enter fit(resume=True)
                    # in-process from the latest durable checkpoint.
                    maybe_elastic_restart(step=host_step)
                    if preemption_requested():
                        # Graceful preemption (SIGTERM): checkpoint mid-epoch
                        # and leave via Preempted; the gang worker converts it
                        # to EXIT_PREEMPTED (restart outside the crash
                        # budget). The finally block joins the async writer.
                        if ckpt:
                            t0 = time.monotonic()
                            ckpt.save(state, host_step,
                                      metadata={"epoch": epoch,
                                                "preempted": True,
                                                "callbacks": sched.state_dicts()})
                            sp.span("ckpt_save", t0, time.monotonic(),
                                    epoch_id,
                                    args=sp.on and {"step": host_step})
                        raise Preempted(host_step)
                    lr = sched.lr_for_batch(epoch, step_i, steps_per_epoch)
                    if lr is not None:
                        state = set_lr(state, lr)
                    t_disp = time.monotonic()
                    if self.pp:  # the pipeline step is deterministic: no rng
                        state, m = step(state, inputs, targets)
                    elif chained:
                        # [k, B, S] super-batch through the fused scan
                        # program; metrics come back [k] per step
                        state, m = chain(state, inputs, targets,
                                         jax.random.fold_in(step_rng,
                                                            host_step))
                    else:
                        state, m = step(state, inputs, targets,
                                        jax.random.fold_in(step_rng,
                                                           host_step))
                    t_end = time.monotonic()
                    # enqueue plus back-pressure from the device queue
                    sp.span("dispatch", t_disp, t_end, chain_id,
                            args=sp.on and {"step": host_step, "k": k_chain})
                    # the chain boundary as the host sees it; its self time
                    # (less data_wait and dispatch) is the loop's own work
                    sp.span("train_chain", t_chain, t_end, epoch_id, chain_id,
                            args=sp.on and {"epoch": epoch, "step": host_step,
                                            "k": k_chain,
                                            "chained": bool(chained)})
                    if hub is not None:
                        hub.observe("train.chain_ms", (t_end - t_chain) * 1e3)
                    host_step += k_chain
                    step_i += k_chain
                    tlosses.append(m["loss"])
                    taccs.append(m["accuracy"])

                vlosses, vaccs = [], []
                eval_state = state
                if self.sharded:
                    # eval reads only params: dropping the sharded moments
                    # keeps the eval jit from all-gathering them to match
                    # its replicated in-spec (FSDP params DO get gathered —
                    # eval wants full weights)
                    eval_state = eval_state.replace(opt_state=())
                if cfg.ema_decay:
                    # evaluate the Polyak shadow (what serving should ship)
                    eval_state = eval_state.replace(
                        params=ema_params(state), opt_state=())
                # the first wait holds the building of the epoch's
                # validation loader (fit_tables makes one anew every epoch)
                t0 = t_val = time.monotonic()
                val_id = sp.open()
                for i, (vin, vtg) in enumerate(val_batches()):
                    t1 = time.monotonic()
                    sp.span("val_data_wait", t0, t1, val_id,
                            args=sp.on and {"i": i, "first": i == 0})
                    vm = eval_step(eval_state, vin, vtg)
                    vlosses.append(vm["loss"])
                    vaccs.append(vm["accuracy"])
                    t0 = time.monotonic()
                    sp.span("val_dispatch", t1, t0, val_id,
                            args=sp.on and {"i": i})
                sp.span("validation", t_val, t0, epoch_id, val_id,
                        args=sp.on and {"steps": len(vlosses)})
                # ONE device reduction + fetch per metric for the whole epoch
                # (fetch_metrics_mean) instead of a device_get per scalar —
                # exact per-step mean whether entries are scalars or [k]
                # chain arrays. The first fetch is the epoch's barrier: it
                # returns when the device has run every step before it.
                t0 = time.monotonic()
                row = {
                    "epoch": epoch,
                    "loss": fetch_metrics_mean(tlosses),
                    "accuracy": fetch_metrics_mean(taccs),
                    "val_loss": fetch_metrics_mean(vlosses),
                    "val_accuracy": fetch_metrics_mean(vaccs),
                    "lr": get_lr(state),
                }
                if self.pp:  # schedule idle fraction, logged beside loss
                    row["pp_bubble_fraction"] = float(
                        jax.device_get(m["pp_bubble_fraction"]))
                t1 = time.monotonic()
                sp.span("epoch_fetch", t0, t1, epoch_id)
                history.append(row)
                epochs_run = epoch + 1
                if self.run is not None:
                    self.run.log_metrics(row, step=epoch)
                t0 = time.monotonic()
                sp.span("epoch_report", t1, t0, epoch_id)

                # Callbacks consume this epoch's metrics FIRST, then the
                # checkpoint saves the post-callback counters/LR — resume =
                # continuation (ScheduleSuite holds the ordering rules).
                end_id = sp.open()
                state, stop = sched.epoch_end(state, row["val_loss"], epoch)
                if ckpt and (epoch + 1) % cfg.checkpoint_every_epochs == 0:
                    t_ck = time.monotonic()
                    ckpt.save(state, host_step,
                              metadata={"epoch": epoch,
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
                sp.span("epoch_end", t0, t1, epoch_id, end_id)
                sp.span("epoch", t_epoch, t1, span=epoch_id,
                        args=sp.on and {"epoch": epoch,
                                        "steps": steps_per_epoch})
                if stop:
                    break
        finally:
            if ckpt:
                ckpt.close()
            if best is not None:
                best.close()

        last = history[-1] if history else {"val_loss": float("nan"),
                                            "val_accuracy": float("nan")}
        return LMTrainResult(val_loss=last["val_loss"],
                             val_accuracy=last["val_accuracy"],
                             history=history, state=state,
                             epochs_run=epochs_run)
