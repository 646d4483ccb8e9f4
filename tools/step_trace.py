"""Capture jax.profiler traces of the transformer bench steps on the chip.

ViT and LM ran at ~16% MFU against much higher roofline ceilings (ROADMAP
S3/S4) — implementation, not physics. A bench row gives one number per
config; this tool captures the per-op breakdown that says WHERE the time
goes: it builds the exact bench-shape train steps (``vit``, ``lm_flash``) and
runs ``--steps`` of them under ``jax.profiler.trace``, writing
TensorBoard/perfetto protobufs to ``benchruns/traces/<config>/`` for
``tools/trace_summary.py``.

Usage: ``python tools/step_trace.py [vit lm_flash]``
CI smoke: ``DDW_BENCH_SMOKE=1`` shrinks shapes (trace machinery still runs).
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import bench  # bench-shape builders + SMOKE sizing
from ddw_tpu.utils.config import require_tpu_or_exit


def _trace_step(name: str, step_fn, state, args, out_root: str,
                n_steps: int) -> dict:
    out_dir = os.path.join(out_root, name)
    os.makedirs(out_dir, exist_ok=True)
    state, metrics = step_fn(state, *args)  # warmup outside the trace
    np.asarray(metrics["loss"])
    t0 = time.perf_counter()
    with jax.profiler.trace(out_dir):
        for _ in range(n_steps):
            state, metrics = step_fn(state, *args)
        np.asarray(metrics["loss"])
    dt = time.perf_counter() - t0
    print(f"[trace] {name}: {n_steps} steps in {dt:.2f}s -> {out_dir}",
          file=sys.stderr, flush=True)
    return {"steps": n_steps, "seconds": round(dt, 3), "dir": out_dir}


def build_vit():
    from ddw_tpu.models.registry import build_model
    from ddw_tpu.runtime.mesh import DATA_AXIS, MeshSpec, make_mesh
    from ddw_tpu.train.step import (batch_sharding, init_state,
                                    make_train_step, replicated_sharding)
    from ddw_tpu.utils.config import ModelCfg, TrainCfg

    img, batch = ((64, 64, 3), 8) if bench.SMOKE else ((224, 224, 3), 256)
    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)), devices=jax.devices())
    mcfg = ModelCfg(name="vit", num_classes=5, dropout=0.5, dtype="bfloat16")
    model = build_model(mcfg)
    tcfg = TrainCfg(batch_size=batch, optimizer="adam", learning_rate=1e-3)
    state, tx = init_state(model, mcfg, tcfg, img, jax.random.PRNGKey(0))
    step = make_train_step(model, tx, mesh, DATA_AXIS, donate=True)
    rng = np.random.RandomState(0)
    n = batch * jax.device_count()
    imgs = jax.device_put(rng.rand(n, *img).astype(np.float32) * 2 - 1,
                          batch_sharding(mesh, DATA_AXIS))
    lbls = jax.device_put(rng.randint(0, 5, (n,)).astype(np.int32),
                          batch_sharding(mesh, DATA_AXIS))
    state = jax.device_put(state, replicated_sharding(mesh))
    return step, state, (imgs, lbls, jax.random.PRNGKey(1))


def build_lm():
    import optax

    from ddw_tpu.models.lm import TransformerLM
    from ddw_tpu.runtime.mesh import DATA_AXIS, MeshSpec, make_mesh
    from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
    from ddw_tpu.train.step import replicated_sharding

    kw = (dict(batch=8, seq=128, hidden=64, depth=2, heads=4, vocab=256)
          if bench.SMOKE else
          dict(batch=8, seq=2048, hidden=512, depth=6, heads=8, vocab=8192))
    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)), devices=jax.devices())
    model = TransformerLM(vocab_size=kw["vocab"], max_len=kw["seq"],
                          hidden=kw["hidden"], depth=kw["depth"],
                          num_heads=kw["heads"], mlp_dim=kw["hidden"] * 4,
                          dropout=0.0, dtype=jnp.bfloat16, seq_axis=None)
    tx = optax.adam(3e-4)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0), seq_len=8)
    step = make_lm_train_step(model, tx, mesh, DATA_AXIS, seq_axis=None,
                              donate=True)
    rng = np.random.RandomState(0)
    n = kw["batch"] * jax.device_count()
    toks = rng.randint(0, kw["vocab"], (n, kw["seq"] + 1)).astype(np.int32)
    inputs = jax.device_put(toks[:, :-1], step.batch_sharding)
    targets = jax.device_put(toks[:, 1:], step.batch_sharding)
    state = jax.device_put(state, replicated_sharding(mesh))
    return step, state, (inputs, targets, jax.random.PRNGKey(1))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("configs", nargs="*", default=["vit", "lm_flash"])
    ap.add_argument("--steps", type=int, default=2 if bench.SMOKE else 10)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "benchruns",
        "traces"))
    args = ap.parse_args()
    kind = require_tpu_or_exit("trace")
    print(f"device: {kind}", file=sys.stderr, flush=True)

    builders = {"vit": build_vit, "lm_flash": build_lm}
    unknown = set(args.configs) - set(builders)
    if unknown:
        raise SystemExit(f"unknown configs {sorted(unknown)}; "
                         f"have {sorted(builders)}")
    result = {"device": kind}
    for name in args.configs:
        step, state, call_args = builders[name]()
        result[name] = _trace_step(name, step, state, call_args, args.out,
                                   args.steps)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
