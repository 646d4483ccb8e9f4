#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two paths that run on a TPU once, through the entry points a user
calls, at the full width of the models the repo trains and serves; depth and
step counts are cut, weights are random from a seed. Three legs, one process,
on whatever ``jax.devices()`` gives:

1. ``Trainer.fit`` — MobileNetV2 at the reference's transfer shape over a
   seeded synthetic flowers table written by ``data/prep.py`` and read back
   through ``ShardedLoader`` (native JPEG decode, host->device prefetch);
2. ``LMTrainer.fit_tables`` — the widest LM the repo runs, at a sequence
   (4096) past the attention dispatch's crossover, so the step runs the
   three Pallas flash kernels (forward, dQ, dK/dV) in every layer;
3. ``save_lm_package`` -> ``load_lm_package`` -> ``ServingEngine`` with default
   ``EngineCfg``: warm-up, then concurrent ``submit_generate`` requests of
   mixed prompt length, checked against ``pkg.generate`` (see ``_check_tokens``).
   With four chips a second engine runs the same requests at ``tp=4``.

The trainers use every visible chip (the data-parallel run on a four-chip
host); the engines use the first chip, or four for ``tp=4``. No leg is wrapped
in a handler: one that raises ends the run with a traceback and a nonzero
status, and the last line is then not a result.

    python chip_smoke.py               # on the chip; anywhere else it refuses
    python chip_smoke.py --rehearsal   # the same legs at a tiny size, CPU only

The last line of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A rehearsal prints ``{"rehearsal": true, ...}`` instead and never ``"ok"``.
Seconds reported per leg are observations for CHANGES.md, not metrics:
``setup_s`` is everything up to the end of the first (compiling) epoch or the
end of warm-up, ``run_s`` the warm second epoch or the requests themselves.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import tempfile
import time

# One table for every size in this file. "chip" is what ISSUE 21 / ROADMAP
# name as full width; "rehearsal" is the same program small enough for the
# CPU sandbox (on-chip-measurement guide: run it here first at a tiny size).
SHAPES = {
    "chip": {
        "vision": dict(width_mult=1.0, img=224, dtype="bfloat16",
                       batch_per_chip=256, steps_per_epoch=4, epochs=2,
                       loader_workers=8),
        "lm": dict(vocab=8192, hidden=512, depth=6, heads=8, mlp=2048,
                   dtype="bfloat16", seq=4096, batch_per_chip=8,
                   steps_per_epoch=4, epochs=2),
        "serve": dict(prompt_ranges=((17, 32), (130, 256), (1030, 1500)),
                      requests_per_range=12, steps=32),
    },
    "rehearsal": {
        "vision": dict(width_mult=0.35, img=32, dtype="float32",
                       batch_per_chip=8, steps_per_epoch=2, epochs=2,
                       loader_workers=2),
        "lm": dict(vocab=256, hidden=64, depth=2, heads=4, mlp=128,
                   dtype="float32", seq=256, batch_per_chip=2,
                   steps_per_epoch=2, epochs=2),
        "serve": dict(prompt_ranges=((5, 8), (20, 32), (70, 100)),
                      requests_per_range=3, steps=8),
    },
}
NUM_CLASSES = 5

# Engine-vs-sequential check (see _check_tokens): how far below the reference
# forward's best logit an emitted token may sit. bf16 activations carry 8
# mantissa bits (logits of a few units round at ~0.016), so two correct
# programs that batch differently may flip a near-tie; a wrong cache row or
# position lands whole logits away (random-model logits spread over several
# units). Worst gap seen on a v5e: 0.011 (PERF.md, PR 21).
LOGIT_TOL = {"bfloat16": 0.0625, "float32": 1e-3}


def _emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _epoch_end_times(run) -> dict[int, float]:
    """Wall time at which each epoch's metrics were logged, from the tracker
    run's own ``metrics.jsonl`` (``ts`` per record, ``step`` = epoch)."""
    ends: dict[int, float] = {}
    with open(os.path.join(run.run_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            ends[rec["step"]] = max(ends.get(rec["step"], 0.0), rec["ts"])
    return ends


def _trainer_report(t0: float, t_tables: float, run, result,
                    expect_steps: int, n_dev: int, loss_ref: float) -> dict:
    import jax

    ends = _epoch_end_times(run)
    losses = [float(v) for row in result.history
              for v in (row["loss"], row["val_loss"])]
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss in {result.history}")
    # a randomly initialised C-way classifier starts at cross-entropy ln C
    first = result.history[0]["loss"]
    if not 0.1 * loss_ref < first < 10.0 * loss_ref:
        raise AssertionError(f"first-epoch loss {first:.3f} is not near the "
                             f"random-model reference {loss_ref:.3f}")
    steps = int(jax.device_get(result.state.step))
    if steps != expect_steps:
        raise AssertionError(f"state.step {steps} != {expect_steps}")
    on = {len(leaf.sharding.device_set)
          for leaf in jax.tree.leaves(result.state.params)}
    if on != {n_dev}:
        raise AssertionError(f"params live on {on} devices, expected {n_dev}")
    return {
        "setup_s": round(ends[0] - t0, 2),
        "tables_s": round(t_tables - t0, 2),    # of setup_s: writing tables
        "run_s": round(ends[max(ends)] - ends[max(ends) - 1], 2),
        "train_steps": steps, "val_steps": len(result.history),
        "first_epoch_loss": round(first, 4), "loss_reference": round(loss_ref, 4),
        "last_epoch_loss": round(result.history[-1]["loss"], 4),
        "val_loss": round(result.val_loss, 4),
        "state_on_devices": n_dev,
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()],
    }


def vision_leg(shape: dict, work: str, tracker) -> dict:
    import jax

    from ddw_tpu.data.prep import generate_synthetic_flowers, prepare_flowers
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.native.decode import native_available
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.trainer import Trainer
    from ddw_tpu.utils.config import DataCfg, ModelCfg, TrainCfg

    t0 = time.time()
    native_available()          # builds libddwpipeline.so, or raises g++'s words
    n_dev = len(jax.devices())
    global_batch = shape["batch_per_chip"] * n_dev
    # steps_per_epoch train batches + one validation batch, a few to spare so
    # the seeded split's floor cannot come up one image short
    spe = shape["steps_per_epoch"]
    per_class = -(-(spe + 1) * global_batch // NUM_CLASSES) + 2
    src = generate_synthetic_flowers(os.path.join(work, "flowers"),
                                     images_per_class=per_class,
                                     size=shape["img"])
    store = TableStore(os.path.join(work, "tables"))
    train_tbl, val_tbl, _ = prepare_flowers(
        src, store, sample_fraction=1.0, train_fraction=spe / (spe + 1),
        shard_size=256)
    if (train_tbl.num_records // global_batch != spe
            or val_tbl.num_records // global_batch != 1):
        raise AssertionError(f"split {train_tbl.num_records} train / "
                             f"{val_tbl.num_records} val does not give {spe} "
                             f"train steps and 1 val step at batch "
                             f"{global_batch}")
    t_tables = time.time()

    data_cfg = DataCfg(img_height=shape["img"], img_width=shape["img"],
                       loader_workers=shape["loader_workers"])
    model_cfg = ModelCfg(name="mobilenet_v2", num_classes=NUM_CLASSES,
                         width_mult=shape["width_mult"], dtype=shape["dtype"],
                         freeze_base=False)         # full backward
    train_cfg = TrainCfg(batch_size=shape["batch_per_chip"],
                         epochs=shape["epochs"], warmup_epochs=0, seed=0)
    run = tracker.start_run("chip_smoke_vision")
    trainer = Trainer(data_cfg, model_cfg, train_cfg,
                      mesh=make_data_mesh(jax.devices()), run=run)
    result = trainer.fit(train_tbl, val_tbl)
    run.end()
    return {"leg": "vision_trainer", "global_batch": global_batch,
            "table_records": [train_tbl.num_records, val_tbl.num_records],
            **_trainer_report(t0, t_tables, run, result,
                              spe * shape["epochs"], n_dev,
                              math.log(NUM_CLASSES))}


def lm_leg(shape: dict, work: str, tracker):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ddw_tpu.data.prep import write_token_table
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.ops.flash_attention import _attn_impl
    from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
    from ddw_tpu.train.lm_trainer import LMTrainer
    from ddw_tpu.train.step import make_optimizer
    from ddw_tpu.utils.config import LMCfg, TrainCfg

    t0 = time.time()
    n_dev = len(jax.devices())
    global_batch = shape["batch_per_chip"] * n_dev
    seq, vocab = shape["seq"], shape["vocab"]
    # arithmetic sequences mod vocab (examples/11_lm_lifecycle.py): structure
    # a model can learn, drawn from a seed
    rng = np.random.RandomState(0)
    n_seqs = (shape["steps_per_epoch"] + 1) * global_batch
    corpus = ((rng.randint(0, vocab, (n_seqs, 1))
               + rng.randint(1, 5, (n_seqs, 1)) * np.arange(seq + 1)[None])
              % vocab).astype(np.int32)
    store = TableStore(os.path.join(work, "lm_tables"))
    train_tbl = write_token_table(store, "lm_train", corpus[global_batch:])
    val_tbl = write_token_table(store, "lm_val", corpus[:global_batch])
    t_tables = time.time()

    lm_cfg = LMCfg(vocab_size=vocab, max_len=seq, hidden=shape["hidden"],
                   depth=shape["depth"], num_heads=shape["heads"],
                   mlp_dim=shape["mlp"], dropout=0.0, dtype=shape["dtype"])
    train_cfg = TrainCfg(batch_size=shape["batch_per_chip"],
                         epochs=shape["epochs"], warmup_epochs=0,
                         learning_rate=3e-4, seed=0)
    run = tracker.start_run("chip_smoke_lm")
    trainer = LMTrainer(lm_cfg, train_cfg, run=run)
    result = trainer.fit_tables(train_tbl, val_tbl)
    run.end()
    report = _trainer_report(t0, t_tables, run, result,
                             shape["steps_per_epoch"] * shape["epochs"],
                             n_dev, math.log(vocab))

    # Which attention tier the step lowered to: the dispatch's own answer at
    # the per-device shape, and the flash kernels in the lowered step: one
    # Mosaic body for the forward and one for the one-pass backward, shared
    # by the layers (none when the CPU interprets them), and a call of each
    # a layer.
    head_dim = shape["hidden"] // shape["heads"]
    qk = jax.ShapeDtypeStruct(
        (shape["batch_per_chip"], shape["heads"], seq, head_dim),
        jnp.dtype(shape["dtype"]))
    tier = _attn_impl(qk, qk, "auto")
    tx = make_optimizer(train_cfg)
    step = make_lm_train_step(trainer.model, tx, trainer.mesh, seq_axis=None)
    state = jax.eval_shape(lambda: init_lm_state(
        trainer.model, tx, jax.random.PRNGKey(0)))
    toks = jax.ShapeDtypeStruct((global_batch, seq), jnp.int32)
    lowered = step.lower(state, toks, toks, jax.random.PRNGKey(0)).as_text()
    mosaic_bodies = lowered.count("tpu_custom_call")
    kernel_calls = len(re.findall(r"call @_flash_(?:forward|bwd)\b",
                                  lowered))
    if tier != "pallas":
        raise AssertionError(f"attention tier is {tier!r}, not 'pallas' — "
                             f"the shape no longer exercises the kernels")
    if kernel_calls != 2 * shape["depth"] or (
            jax.default_backend() != "cpu" and mosaic_bodies != 2):
        raise AssertionError(
            f"{kernel_calls} flash kernel calls over {mosaic_bodies} Mosaic "
            f"bodies in the lowered step, expected {2 * shape['depth']} "
            f"over 2")
    report.update(attention_tier=tier, flash_kernel_calls=kernel_calls,
                  mosaic_kernel_bodies=mosaic_bodies)
    return ({"leg": "lm_trainer", "global_batch": global_batch, "seq": seq,
             **report}, lm_cfg, result.state.params)


def _check_tokens(pkg, prompts, refs, outs, steps: int, tol: float) -> dict:
    """The engine-vs-sequential check.

    The repo's contract is token identity between the batched engine and
    sequential ``pkg.generate``; it is exact in float32. In bf16 a 16-row
    matmul and a 1-row one need not round alike and a seeded random model has
    near-tied logits, so identity is reported (``identical``) but the gate is
    one a correct program passes every time: teacher-force each engine output
    through the package's own forward and require every emitted token to sit
    within ``tol`` of that position's best logit. A token that is not the
    reference's argmax must therefore be a near-tie with it; everything else
    — a wrong cache row, position or prompt — is whole logits away.
    """
    import jax
    import numpy as np

    from ddw_tpu.serve.bucketing import bucket_len, pad_to_bucket

    @jax.jit
    def window_logits(params, tokens, start):
        logits = pkg.model.apply({"params": params}, tokens, train=False)
        return jax.lax.dynamic_slice_in_dim(logits[0], start, steps, axis=0)

    identical, worst = 0, 0.0
    for prompt, ref, out in zip(prompts, refs, outs):
        if out.shape != (steps,):
            raise AssertionError(f"engine returned shape {out.shape}")
        identical += bool(np.array_equal(out, ref))
        seq = np.concatenate([prompt, out[:-1]])[None]
        padded = pad_to_bucket(seq, bucket_len(seq.shape[1],
                                               pkg.lm_cfg.max_len))
        logits = np.asarray(
            window_logits(pkg.params, padded, len(prompt) - 1))
        gap = logits.max(axis=-1) - logits[np.arange(steps), out]
        worst = max(worst, float(gap.max()))
    if not worst <= tol:
        raise AssertionError(f"an engine token sits {worst:.4f} below the "
                             f"reference's best logit (tolerance {tol})")
    return {"identical": identical, "of": len(prompts),
            "worst_logit_gap": round(worst, 5), "logit_tol": tol}


def serve_leg(shape: dict, work: str, lm_cfg, params, tp: int) -> dict:
    import numpy as np

    from ddw_tpu.serve import EngineCfg, ServingEngine
    from ddw_tpu.serving.lm_package import load_lm_package, save_lm_package

    t0 = time.time()
    pkg = load_lm_package(save_lm_package(
        os.path.join(work, f"lm_package_tp{tp}"), lm_cfg, params))
    rng = np.random.RandomState(1)
    lens = [int(rng.randint(lo, hi + 1)) for lo, hi in shape["prompt_ranges"]
            for _ in range(shape["requests_per_range"])]
    rng.shuffle(lens)
    prompts = [rng.randint(0, lm_cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    steps = shape["steps"]

    with ServingEngine(lm=pkg, cfg=EngineCfg(tp=tp)) as eng:
        eng.warmup(sorted(set(lens)))
        t_warm = time.time()
        futs = [eng.submit_generate(p, steps) for p in prompts]
        outs = [f.result(timeout=600).tokens for f in futs]
        t_done = time.time()
        deadline = time.time() + 10.0      # gauges are pushed once per tick
        while (eng.snapshot().get("serve.blocks_used") != 0.0
               and time.time() < deadline):
            time.sleep(0.05)
        snap = eng.snapshot()
    if snap["serve.completed"] != len(prompts):
        raise AssertionError(f"serve.completed {snap['serve.completed']} != "
                             f"{len(prompts)} sent")
    if snap["serve.blocks_used"] != 0.0:
        raise AssertionError(f"serve.blocks_used {snap['serve.blocks_used']} "
                             f"after every request finished (leak)")
    if tp > 1 and (snap["serve.tp_degree"] != tp
                   or not snap["serve.tp_dispatches"] > 0):
        raise AssertionError(f"tp={tp} engine reports tp_degree "
                             f"{snap['serve.tp_degree']}, tp_dispatches "
                             f"{snap['serve.tp_dispatches']}")

    t_check = time.time()
    refs = [pkg.generate(p[None, :], steps)[0] for p in prompts]
    check = _check_tokens(pkg, prompts, refs, outs, steps,
                          LOGIT_TOL[lm_cfg.dtype])
    return {"leg": f"serving_engine_tp{tp}", "requests": len(prompts),
            "prompt_len_min_max": [min(lens), max(lens)], "steps": steps,
            "setup_s": round(t_warm - t0, 2),
            "run_s": round(t_done - t_warm, 2),
            "check_s": round(time.time() - t_check, 2),
            "completed": snap["serve.completed"],
            "blocks_used_after": snap["serve.blocks_used"],
            "prefills": snap["serve.prefills"],
            "decode_ticks": snap["serve.decode_ticks"],
            "ttft_ms_p99": round(snap.get("serve.ttft_ms_p99", 0.0), 2),
            "check": check}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny shapes on the CPU; never prints a chip pass")
    args = ap.parse_args(argv)
    mode = "rehearsal" if args.rehearsal else "chip"
    if args.rehearsal:
        # the rehearsal keeps the LM step on the Pallas tier (interpreted on
        # the CPU) at its tiny shape; read when ddw_tpu.ops is imported
        os.environ["DDW_ATTN_XLA_PLAIN_MAX"] = "0"
        os.environ["DDW_ATTN_XLA_CKPT_MAX"] = "0"

    import jax
    import jaxlib

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:   # the platform check below decides
        libtpu = None
    print(f"chip_smoke[{mode}]: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}", flush=True)
    want = "cpu" if args.rehearsal else "tpu"
    if device["platform"] != want:
        print(f"chip_smoke[{mode}]: needs JAX platform {want!r}, found "
              f"{device['platform']!r} ({device['kind']}); refusing",
              file=sys.stderr, flush=True)
        return 1

    from ddw_tpu.tracking.tracker import Tracker
    from ddw_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    shapes = SHAPES[mode]
    t_start = time.time()
    with tempfile.TemporaryDirectory(prefix="ddw_chip_smoke_") as work:
        tracker = Tracker(os.path.join(work, "runs"), "chip_smoke")
        _emit(vision_leg(shapes["vision"], work, tracker))
        lm_report, lm_cfg, params = lm_leg(shapes["lm"], work, tracker)
        _emit(lm_report)
        _emit(serve_leg(shapes["serve"], work, lm_cfg, params, tp=1))
        if device["count"] >= 4:
            _emit(serve_leg(shapes["serve"], work, lm_cfg, params, tp=4))
    _emit({"summary": mode, "total_s": round(time.time() - t_start, 1),
           "compile_cache_dir": cache_dir})
    if args.rehearsal:
        _emit({"rehearsal": True, "legs_passed": True, "device": device})
    else:
        _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
