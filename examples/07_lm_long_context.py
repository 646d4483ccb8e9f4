"""Long-context LM training — DP x sequence parallelism over a device mesh.

Beyond-parity example (the reference workshop has no language model — SURVEY.md
§5 "Long-context ... Absent"): trains a character-level TransformerLM on
synthetic text with the sequence axis sharded across devices, so the context
length scales with the mesh instead of one device's memory. Attention runs as a
``ppermute`` ring (ddw_tpu.parallel.ring_attention); the full train step —
forward, backward, gradient pmean over data x seq — is one jitted XLA program.

Run (virtual 8-device CPU mesh):
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/07_lm_long_context.py --quick

Args: lm.key=value overrides (e.g. lm.hidden=512), train.* for the loop,
--seq-devices to size the seq axis (default: half the devices),
--moe to route the MLPs through Switch experts partitioned over the data axis
(expert parallelism: lax.all_to_all token exchange), --pipeline to train the
same model under the GPipe pipeline schedule instead (stages over the mesh).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from ddw_tpu.models.lm import build_lm
from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS, SEQ_AXIS
from ddw_tpu.train.lm_step import init_lm_state, make_lm_eval_step, make_lm_train_step
from ddw_tpu.train.step import make_optimizer
from ddw_tpu.utils.compile_cache import enable_compile_cache
from ddw_tpu.utils.config import LMCfg, TrainCfg, apply_overrides


def synthetic_text(rng: np.random.RandomState, n_seqs: int, seq_len: int,
                   vocab: int) -> np.ndarray:
    """Deterministic-ish token streams: a noisy affine successor process, so the
    next token is predictable and the loss curve means something."""
    step = rng.randint(1, vocab - 1)
    start = rng.randint(0, vocab, size=(n_seqs, 1))
    seq = (start + step * np.arange(seq_len + 1)[None, :]) % vocab
    noise = rng.rand(n_seqs, seq_len + 1) < 0.05
    seq = np.where(noise, rng.randint(0, vocab, size=seq.shape), seq)
    return seq.astype(np.int32)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="tiny model + few steps")
    ap.add_argument("--seq-devices", type=int, default=0,
                    help="devices on the seq axis (0 = half the mesh)")
    ap.add_argument("--moe", type=int, default=0, metavar="E",
                    help="route MLPs through E Switch experts, partitioned "
                         "over the data axis (expert parallelism)")
    ap.add_argument("--pipeline", type=int, default=0, metavar="STAGES",
                    help="train under the GPipe pipeline schedule with this "
                         "many stages instead of DPxSP")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--speculative", action="store_true",
                    help="also decode via draft-verified speculative rounds")
    ap.add_argument("--trainer", action="store_true",
                    help="train via LMTrainer (epochs, checkpoints, tracker, "
                         "LR schedules) instead of the raw step loop")
    ap.add_argument("overrides", nargs="*", help="lm.key=value / train.key=value")
    args = ap.parse_args()
    enable_compile_cache()

    cfgs = {"lm": LMCfg(), "train": TrainCfg(warmup_epochs=0)}
    if args.quick:
        cfgs["lm"].hidden, cfgs["lm"].depth, cfgs["lm"].mlp_dim = 64, 2, 128
        cfgs["lm"].vocab_size, cfgs["lm"].max_len = 64, 512
        cfgs["lm"].dtype = "float32"
    apply_overrides(cfgs, args.overrides)
    lm_cfg, train_cfg = cfgs["lm"], cfgs["train"]

    devices = jax.devices()
    n = len(devices)
    sp = args.seq_devices or max(1, n // 2)
    dp = n // sp
    assert dp * sp == n, f"seq devices {sp} must divide device count {n}"

    if args.trainer:
        # The managed path: LMTrainer carries the vision Trainer's amenities
        # (epoch loop, LR schedules, checkpoints, tracker) over the DPxSP
        # LM step — same contracts, token-array data model.
        from ddw_tpu.train.lm_trainer import LMTrainer

        if args.moe:
            lm_cfg.num_experts = args.moe  # MoE composes with the trainer
        if args.pipeline:
            # The managed pipeline path: train.pipeline_stages builds the
            # (data, pipe) mesh and the trainer drives the GPipe step
            # (ddw_tpu/train/lm_trainer.py; schedule knobs on TrainCfg).
            lm_cfg.dropout = 0.0  # the pipeline step is deterministic
            train_cfg.pipeline_stages = args.pipeline
            if lm_cfg.depth % args.pipeline:
                adjusted = max(args.pipeline,
                               lm_cfg.depth // args.pipeline * args.pipeline)
                print(f"[pipeline] adjusting lm.depth {lm_cfg.depth} -> "
                      f"{adjusted} (must divide {args.pipeline} stages)")
                lm_cfg.depth = adjusted
            mb = train_cfg.pipeline_microbatches
            if mb < 1 or train_cfg.batch_size % mb:
                fixed = next(c for c in range(min(max(mb, 1),
                                                  train_cfg.batch_size), 0, -1)
                             if train_cfg.batch_size % c == 0)
                print(f"[pipeline] adjusting pipeline_microbatches {mb} -> "
                      f"{fixed} (must divide batch_size "
                      f"{train_cfg.batch_size})")
                train_cfg.pipeline_microbatches = fixed
            # the pipeline shards depth, not sequence; dp comes from the
            # devices the trainer will actually use
            eff_n = train_cfg.num_devices or n
            sp, dp = 1, eff_n // args.pipeline
        if args.speculative or args.steps:
            raise SystemExit("--trainer runs epochs, not --steps, and skips "
                             "the generation demos — use train.epochs=N, and "
                             "run --speculative without --trainer (or see "
                             "examples/11_lm_lifecycle.py for the packaged "
                             "speculative path)")

        rng = np.random.RandomState(train_cfg.seed)
        seq_len = min(lm_cfg.max_len - 1, 64 * sp) // sp * sp
        # corpus sized from the mesh: the 0.9 train split must cover at
        # least one global batch (batch_size * dp) at every dp/sp choice
        n_seqs = max(96, 3 * train_cfg.batch_size * dp)
        corpus = synthetic_text(rng, n_seqs, seq_len, lm_cfg.vocab_size)
        res = LMTrainer(lm_cfg, train_cfg, seq_devices=sp).fit(corpus)
        for row in res.history:
            print({k: round(v, 4) if isinstance(v, float) else v
                   for k, v in row.items()})
        layout = (f"pipe={args.pipeline} dp={dp}"
                  if args.pipeline else f"dp={dp} sp={sp}")
        print(f"trainer: mesh {layout} epochs={res.epochs_run} "
              f"val_loss={res.val_loss:.4f} "
              f"val_accuracy={res.val_accuracy:.3f}")
        return

    if args.pipeline:
        # GPipe pipeline schedule: stages over a 'pipe' axis (x DP when the
        # mesh is bigger), stage-sharded stacked block params.
        from ddw_tpu.parallel.pipeline import init_pp_state, make_pp_lm_train_step

        stages = args.pipeline
        dp = n // stages
        assert dp * stages == n, f"stages {stages} must divide devices {n}"
        if lm_cfg.depth % stages:
            adjusted = max(stages, lm_cfg.depth // stages * stages)
            print(f"[pipeline] adjusting lm.depth {lm_cfg.depth} -> {adjusted} "
                  f"(must divide {stages} stages)")
            lm_cfg.depth = adjusted
        axes = ((DATA_AXIS, dp), ("pipe", stages)) if dp > 1 else (("pipe", stages),)
        mesh = make_mesh(MeshSpec(axes), devices=devices)
        lm_cfg.dropout = 0.0
        if args.moe:
            lm_cfg.num_experts = args.moe  # dense experts under PP (EP is
            # make_lm_train_step territory; the PP step rejects expert_axis)
        model = build_lm(lm_cfg)
        tx = make_optimizer(train_cfg)
        state = init_pp_state(model, tx, mesh, jax.random.PRNGKey(train_cfg.seed))
        step_pp = make_pp_lm_train_step(
            model, tx, mesh, data_axis=DATA_AXIS if dp > 1 else None,
            num_microbatches=2)
        state = step_pp.place_state(state)
        step = lambda st, i, t, _rng: step_pp(st, i, t)  # noqa: E731
        eval_step = None
        sp = 1
    else:
        mesh = make_mesh(MeshSpec(((DATA_AXIS, dp), (SEQ_AXIS, sp))), devices=devices)
        seq_axis = SEQ_AXIS if sp > 1 else None
        expert_axis = DATA_AXIS if args.moe else None
        if args.moe:
            lm_cfg.num_experts = args.moe

        model = build_lm(lm_cfg, seq_axis=seq_axis, expert_axis=expert_axis)
        tx = make_optimizer(train_cfg)
        state = init_lm_state(model, tx, jax.random.PRNGKey(train_cfg.seed))
        step = make_lm_train_step(model, tx, mesh, seq_axis=seq_axis,
                                  grad_accum_steps=train_cfg.grad_accum_steps)
        eval_step = make_lm_eval_step(model, mesh, seq_axis=seq_axis)

    # global batch/seq: divisible by the mesh axes
    batch = max(train_cfg.batch_size, dp) // dp * dp
    if args.pipeline:
        # num_microbatches=2 must divide each data shard: round UP to 2*dp
        batch = -(-batch // (2 * dp)) * (2 * dp)
    seq_len = min(lm_cfg.max_len, 64 * sp) // sp * sp
    steps = args.steps or (60 if args.quick else 300)

    rng = np.random.RandomState(train_cfg.seed)
    tokens = synthetic_text(rng, batch, seq_len, lm_cfg.vocab_size)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    print(f"mesh: {dict(mesh.shape)}  global_batch={batch}  seq_len={seq_len}  "
          f"params={sum(x.size for x in jax.tree.leaves(state.params)):,}")
    t0 = time.time()
    for i in range(steps):
        state, metrics = step(state, inputs, targets, jax.random.PRNGKey(i))
        if i % max(1, steps // 6) == 0:
            print(f"step {i:4d}  loss={float(metrics['loss']):.4f}  "
                  f"acc={float(metrics['accuracy']):.3f}")
    jax.block_until_ready(metrics["loss"])
    dt = time.time() - t0
    final = eval_step(state, inputs, targets) if eval_step else metrics
    tok_s = steps * batch * seq_len / dt
    aux = (f" aux={float(metrics['aux_loss']):.3f}"
           if "aux_loss" in metrics else "")
    print(f"final: loss={float(final['loss']):.4f} acc={float(final['accuracy']):.3f} "
          f"tokens/sec={tok_s:,.0f} ({dt:.1f}s for {steps} steps){aux}")

    # KV-cached greedy continuation (decode path; ddw_tpu.models.lm.generate)
    from ddw_tpu.models.lm import generate, TransformerLM  # noqa: F401

    params = state.params
    if args.pipeline:
        from ddw_tpu.parallel.pipeline import lm_params_from_pp

        params = lm_params_from_pp(jax.device_get(params), args.pipeline,
                                   model.depth)
    prompt = tokens[:1, :16]
    cont = np.asarray(generate(model, params, prompt, num_steps=16))
    match = float((cont[0] == tokens[0, 16:32]).mean())
    print(f"generate: 16-token greedy continuation matches training stream "
          f"{match:.0%}")

    if args.speculative:
        # Draft-verified decoding (ddw_tpu.models.spec_decode): the trained
        # model drafts for itself — a correctness/latency demonstration; a
        # real deployment pairs a small draft with a large target.
        from ddw_tpu.models.spec_decode import generate_speculative

        spec, stats = generate_speculative(model, params, model, params,
                                           prompt, num_steps=16, k=4)
        assert (np.asarray(spec) == cont).all(), "spec decode diverged"
        print(f"speculative: identical 16 tokens in {stats['target_calls']} "
              f"target calls incl. prefill (acceptance "
              f"{stats['acceptance_rate']:.0%}, "
              f"{stats['tokens_per_target_call']:.1f} tok/call; plain greedy "
              f"= 1.0)")


if __name__ == "__main__":
    main()
