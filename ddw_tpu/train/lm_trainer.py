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

import time
import warnings

import jax
import numpy as np

from ddw_tpu.models.lm import build_lm
from ddw_tpu.runtime.elastic import process_topology
from ddw_tpu.runtime.mesh import (DATA_AXIS, PIPE_AXIS, SEQ_AXIS, MeshSpec,
                                  make_data_mesh, make_mesh)
from ddw_tpu.train.lm_step import (
    init_lm_state,
    make_lm_eval_step,
    make_lm_train_chain,
    make_lm_train_step,
)
from ddw_tpu.train import loop
from ddw_tpu.train.loop import TrainResult
from ddw_tpu.train.schedule import ScheduleSuite
from ddw_tpu.train.step import chain_plan, make_optimizer
from ddw_tpu.utils.config import LMCfg, TrainCfg


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
            if lm_cfg.num_experts or lm_cfg.layer.sows:
                raise ValueError(
                    f"{flag} does not support MoE models or layers that "
                    f"choose their keys: the GSPMD step's forward discards "
                    f"what the layers sow (the Switch aux loss, the "
                    f"indexer's KL term), which would silently train an "
                    f"unbalanced router or an untrained indexer — use the "
                    f"plain DP/EP step (no zero/fsdp)")
        if ((self.pp and getattr(lm_cfg, "passes", 1) > 1)
                or ((self.pp or self.sharded)
                    and getattr(lm_cfg, "exit_gate", False))):
            raise NotImplementedError(
                "lm.passes > 1 and lm.exit_gate train through the plain "
                "DP step: the pipeline step builds each stage's blocks once "
                "and runs them once, and neither it nor the zero/fsdp step "
                "descends the exits' expected loss (ROADMAP M11)")
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
            if lm_cfg.layer.sows:
                raise ValueError("pipeline_stages does not support layers "
                                 "that sow a loss term (lm.layer: indexed "
                                 "attention, routed experts): the pipeline "
                                 "step does not collect it")
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
            resume: bool = False) -> TrainResult:
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

        def make_providers(start_epoch, step, plan, chained, tracer):
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

        return self._run(seq_len, steps_per_epoch, global_batch,
                         make_providers, resume, t_fit)

    def fit_tables(self, train_table, val_table,
                   resume: bool = False) -> TrainResult:
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

        def make_providers(start_epoch, step, plan, chained, tracer):
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
                            prefetch_to=prefetch_to, tracer=tracer)
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

            # a fresh unshuffled single pass per epoch, opened by the loop:
            # every eval sees the SAME leading val_steps full batches (no
            # window drift across epochs or resumes)
            val_loader = ShardedLoader(val_table, batch_size=host_batch,
                                       num_epochs=1, num_batches=val_steps,
                                       shuffle=False, **shard_kw)
            return train_batches, val_loader

        return self._run(seq_len, steps_per_epoch, global_batch,
                         make_providers, resume, t_fit)

    def _run(self, seq_len, steps_per_epoch, global_batch, make_providers,
             resume, t_fit) -> TrainResult:
        cfg = self.train_cfg
        mesh = self.mesh
        dp = mesh.shape[DATA_AXIS]
        tracer, sp, setup_id = loop.open_fit(cfg, self.tracer)

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
        chained = any(k > 1 for k in plan)
        rng = jax.random.PRNGKey(cfg.seed)
        t1 = time.monotonic()
        sp.span("optimizer_init", t0, t1, setup_id)
        row_extra = None
        if self.pp:
            from ddw_tpu.parallel.pipeline import (bubble_fraction,
                                                   init_pp_state,
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
            # schedule idle fraction, logged beside loss (the step's own
            # ``pp_bubble_fraction`` metric is this number)
            row_extra = {"pp_bubble_fraction": bubble_fraction(
                cfg.pipeline_stages, cfg.pipeline_microbatches, vstages)}
        else:
            state = init_lm_state(self.model, tx, rng,
                                  seq_len=min(8, seq_len))
            t2 = time.monotonic()
            if self.sharded:
                from ddw_tpu.parallel.zero import (make_fsdp_train_chain,
                                                   make_fsdp_train_step,
                                                   make_zero_train_chain,
                                                   make_zero_train_step)

                make_step, make_chain = (
                    (make_fsdp_train_step, make_fsdp_train_chain) if cfg.fsdp
                    else (make_zero_train_step, make_zero_train_chain))
                # DATA_AXIS, not cfg.data_axis: LMTrainer builds (and
                # validates) its meshes with the constant throughout.
                step = make_step(self.model, tx, mesh, DATA_AXIS,
                                 grad_accum_steps=cfg.grad_accum_steps)
                if chained:
                    chain = make_chain(self.model, tx, mesh, DATA_AXIS,
                                       grad_accum_steps=cfg.grad_accum_steps)
            else:
                step = make_lm_train_step(
                    self.model, tx, mesh, seq_axis=self.seq_axis,
                    grad_accum_steps=cfg.grad_accum_steps,
                    mtp_weight=cfg.mtp_weight,
                    exit_entropy_weight=cfg.exit_entropy_weight)
                if chained:
                    chain = make_lm_train_chain(
                        self.model, tx, mesh, seq_axis=self.seq_axis,
                        grad_accum_steps=cfg.grad_accum_steps,
                        mtp_weight=cfg.mtp_weight,
                        exit_entropy_weight=cfg.exit_entropy_weight)
            # Under ZeRO/FSDP eval reads the sharded params through the
            # shard_map eval step's replicated in-spec: GSPMD gathers per
            # eval call (same trade the vision Trainer makes).
            eval_step = make_lm_eval_step(self.model, mesh,
                                          seq_axis=self.seq_axis)
        run_step = chain if chained else step
        t3 = time.monotonic()
        sp.span("model_init", t1, t2, setup_id)
        sp.span("build_step", t2, t3, setup_id)

        ckpt, best = loop.open_checkpoints(cfg, mesh, DATA_AXIS)
        start_epoch, restored_meta = 0, None
        if ckpt and resume:
            state, start_epoch, restored_meta = loop.restore(
                ckpt, state, steps_per_epoch)
            sp.span("restore", t3, time.monotonic(), setup_id)

        if start_epoch > 0 and start_epoch >= cfg.epochs:
            # The restored checkpoint already covers every requested epoch —
            # the loop below would not run and the result would silently be
            # NaN. Surface the checkpoint's own last metrics so callers
            # gating on val_loss see the real numbers.
            saved = (restored_meta or {}).get("metrics")
            ckpt.close()
            if best is not None:
                best.close()
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
            # Same placement contract as every normal completion: callers
            # that keep training or serving from result.state must not see
            # placement depend on which path returned.
            state = loop.place_state(run_step, state, sp, setup_id)
            return TrainResult(val_loss=saved["val_loss"],
                               val_accuracy=saved["val_accuracy"],
                               history=[saved], state=state,
                               epochs_run=start_epoch)

        # Placement AFTER restore: the checkpoint template is the unplaced
        # pytree; a no-op on a restored already-sharded state.
        state = loop.place_state(run_step, state, sp, setup_id)

        sched = ScheduleSuite.build(cfg, dp, restored_meta)
        loop.log_fit_params(self.run, {"mesh": dict(mesh.shape),
                                       "steps_per_epoch": steps_per_epoch,
                                       "global_batch": global_batch},
                            train=cfg, lm=self.lm_cfg)

        t0 = time.monotonic()
        train_batches, val_batches = make_providers(
            start_epoch, run_step, plan, chained, tracer)
        sp.span("build_loaders", t0, time.monotonic(), setup_id)

        step_rng = jax.random.PRNGKey(cfg.seed + 1)

        def step_args(batch, host_step):
            if self.pp:  # the pipeline step is deterministic: no rng
                return batch
            # the loop's host-side step counter: folding the device's into
            # the rng would be a blocking device_get every step
            return (*batch, jax.random.fold_in(step_rng, host_step))

        return loop.run_epochs(
            cfg=cfg, state=state, sched=sched, plan=plan,
            start_epoch=start_epoch, train_batches=train_batches,
            val_batches=val_batches, step_args=step_args, run_step=run_step,
            eval_step=eval_step, ckpt=ckpt, best=best, run=self.run,
            tracer=tracer, setup_id=setup_id, t_fit=t_fit,
            row_extra=row_extra)
