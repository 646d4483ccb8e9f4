"""Structural (no-chip) evidence for the attention dispatch-tier A/Bs.

A chip A/B of the attention-tier thresholds (``DDW_ATTN_XLA_PLAIN_MAX`` /
``DDW_ATTN_XLA_CKPT_MAX``) times the flips; this tool extracts the half of
the answer that needs no device: for each bench config and each threshold
arm it traces + lowers the EXACT bench train step at headline shapes (CPU,
abstract — no compile, no data) and reports

- which tier ``flash_mha(impl='auto')`` actually picks (recomputed from the
  real q/k shapes via the module's own ``_attn_impl``), and
- the module's ``stablehlo.dot_general`` counts, total and attention-scoped
  (loc metadata) — rematerialization is visible structurally: a
  ``jax.checkpoint`` arm re-runs the attention forward inside the backward,
  so its module carries extra attention dots vs the plain arm.

A tier flip whose module is IDENTICAL to the default's is a no-op arm — the
chip A/B would measure noise; that conclusion needs no chip time.

Usage: ``python tools/attn_dispatch_evidence.py [--configs vit,lm_flash]``
(driver; spawns one subprocess per arm because the thresholds are read at
import). Prints ONE JSON line; human-readable table on stderr.
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import argparse
import json
import re
import subprocess

# the two transformer shapes (vit b256/224², lm_flash b8/S2048/h512);
# DDW_BENCH_SMOKE shrinks them for CI (mechanism only — tiny scores all land
# in the plain tier, so smoke exercises the ckpt_force delta, not the real
# dispatch decisions)
SMOKE = os.environ.get("DDW_BENCH_SMOKE", "").lower() not in ("", "0", "false")
if SMOKE:
    CONFIGS = {
        "vit": dict(batch=8, img=64),
        "lm_flash": dict(batch=4, seq=128, hidden=64, depth=2, heads=4,
                         vocab=256),
    }
else:
    CONFIGS = {
        "vit": dict(batch=256, img=224),
        "lm_flash": dict(batch=8, seq=2048, hidden=512, depth=6, heads=8,
                         vocab=8192),
    }

# arm -> env overrides; thresholds are module-import-time constants
ARMS = {
    "default": {},
    "plain_1g": {"DDW_ATTN_XLA_PLAIN_MAX": str(1024**3)},
    "ckpt_force": {"DDW_ATTN_XLA_PLAIN_MAX": "1"},
}


def lower_bench_step(config: str):
    """Build + abstractly lower the EXACT bench train step for ``config``.

    Shared by this tool and ``tools/mxu_roofline.py`` so the two can never
    lower different programs. Returns ``(lowered_stablehlo_text, dims)``
    where ``dims`` carries the model geometry derived from the REAL model
    object (batch, seqlen, heads, head_dim, hidden, depth, mlp_dim, vocab).
    """
    import jax
    import jax.numpy as jnp

    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS

    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)))
    cfg = CONFIGS[config]

    if config == "vit":
        import warnings

        from ddw_tpu.models.registry import build_model
        from ddw_tpu.train.step import init_state, make_train_step
        from ddw_tpu.utils.config import ModelCfg, TrainCfg

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            mcfg = ModelCfg(name="vit", num_classes=5, dropout=0.5,
                            dtype="bfloat16")
            model = build_model(mcfg)
        tcfg = TrainCfg(batch_size=cfg["batch"], optimizer="adam")
        img = (cfg["img"], cfg["img"], 3)
        state, tx = init_state(model, mcfg, tcfg, img, jax.random.PRNGKey(0))
        step = make_train_step(model, tx, mesh, DATA_AXIS, donate=True)
        b = cfg["batch"]
        args = (state,
                jax.ShapeDtypeStruct((b, *img), jnp.float32),
                jax.ShapeDtypeStruct((b,), jnp.int32),
                jax.random.PRNGKey(1))
        dims = dict(batch=b,
                    # no CLS token in this ViT: patches are mean-pooled
                    # (models/vit.py), S = (img/patch)²
                    seqlen=(cfg["img"] // model.patch) ** 2,
                    heads=model.num_heads,
                    head_dim=model.hidden // model.num_heads,
                    hidden=model.hidden, depth=model.depth,
                    mlp_dim=model.mlp_dim, vocab=model.num_classes)
    else:
        import optax

        from ddw_tpu.models.lm import TransformerLM
        from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step

        model = TransformerLM(vocab_size=cfg["vocab"], max_len=cfg["seq"],
                              hidden=cfg["hidden"], depth=cfg["depth"],
                              num_heads=cfg["heads"],
                              mlp_dim=cfg["hidden"] * 4, dropout=0.0,
                              dtype=jnp.bfloat16, seq_axis=None, remat="none")
        tx = optax.adam(3e-4)
        state = init_lm_state(model, tx, jax.random.PRNGKey(0), seq_len=8)
        step = make_lm_train_step(model, tx, mesh, DATA_AXIS, seq_axis=None,
                                  donate=True)
        b = cfg["batch"]
        args = (state,
                jax.ShapeDtypeStruct((b, cfg["seq"]), jnp.int32),
                jax.ShapeDtypeStruct((b, cfg["seq"]), jnp.int32),
                jax.random.PRNGKey(1))
        dims = dict(batch=b, seqlen=model.max_len, heads=model.num_heads,
                    head_dim=model.hidden // model.num_heads,
                    hidden=model.hidden, depth=model.depth,
                    mlp_dim=model.mlp_dim, vocab=model.vocab_size)
    return step.lower(*args).as_text(), dims


def worker(config: str) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp

    # ddw_tpu.ops re-exports a `flash_attention` FUNCTION that shadows the
    # submodule under `from ... import` — resolve the module itself
    fa = importlib.import_module("ddw_tpu.ops.flash_attention")

    text, d = lower_bench_step(config)
    qk = jax.ShapeDtypeStruct(
        (d["batch"], d["heads"], d["seqlen"], d["head_dim"]), jnp.bfloat16)
    tier = fa._attn_impl(qk, qk, "auto")
    score_mb = d["batch"] * d["heads"] * d["seqlen"] ** 2 * 4 / 1024**2
    dots = len(re.findall(r"stablehlo\.dot_general", text))
    # Attention's QKᵀ / PV matmuls (and their grads/recomputes) are the
    # module's only [B, H]-batched dot_generals — projections contract over
    # hidden with no batching dims. Counting them needs no loc metadata.
    attn_dots = sum(1 for line in text.splitlines()
                    if "stablehlo.dot_general" in line
                    and "batching_dims = [0, 1]" in line)
    return {"config": config, "tier": tier,
            "score_mb": round(score_mb, 1),
            "plain_max_mb": fa._XLA_PLAIN_MAX / 1024**2,
            "ckpt_max_mb": fa._XLA_CKPT_MAX / 1024**2,
            "dot_general": dots, "attn_dot_general": attn_dots,
            "stablehlo_bytes": len(text)}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", default="", help=argparse.SUPPRESS)
    ap.add_argument("--configs", default="vit,lm_flash")
    ap.add_argument("--arms", default=",".join(ARMS))
    args = ap.parse_args()

    if args.worker:
        print(json.dumps(worker(args.worker)))
        return

    out: dict = {"configs": {}}
    for config in args.configs.split(","):
        rows = {}
        for arm in args.arms.split(","):
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       XLA_FLAGS="--xla_force_host_platform_device_count=1",
                       PYTHONPATH=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))))
            # ambient threshold overrides (e.g. exported while trying a
            # queue arm) would silently corrupt the 'default' baseline
            env.pop("DDW_ATTN_XLA_PLAIN_MAX", None)
            env.pop("DDW_ATTN_XLA_CKPT_MAX", None)
            env.update(ARMS[arm])
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--worker", config],
                capture_output=True, text=True, env=env, timeout=1800)
            if r.returncode != 0:
                rows[arm] = {"error": r.stderr[-800:]}
                continue
            rows[arm] = json.loads(r.stdout.strip().splitlines()[-1])
            d = rows[arm]
            print(f"[{config:<8}] {arm:<10} tier={d['tier']:<8} "
                  f"score={d['score_mb']:>7.1f}MB dots={d['dot_general']:>3} "
                  f"attn_dots={d['attn_dot_general']:>3}",
                  file=sys.stderr, flush=True)
        base = rows.get("default", {})
        for arm, d in rows.items():
            if arm != "default" and "dot_general" in d and "dot_general" in base:
                d["no_op_vs_default"] = (
                    d["tier"] == base["tier"]
                    and d["dot_general"] == base["dot_general"])
        out["configs"][config] = rows
    print(json.dumps(out))


if __name__ == "__main__":
    main()
