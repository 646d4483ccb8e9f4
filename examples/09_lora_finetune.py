"""LoRA fine-tuning — the transfer contract, attention-era.

Beyond-parity example: the reference's transfer story is "freeze the
pretrained backbone, train the head" (``02_model_training_single_node.py:
164-178``). For the LM family the same economy comes from LoRA
(ddw_tpu.models.lora): pretrain on a base token process, then adapt to a
shifted task training only rank-r adapters (+ the vocab head) — the training
layer applies the freezing mask automatically when the model carries
``lora_rank``, exactly like ``frozen_prefixes`` does for the CNN families.

Run (virtual 8-device CPU mesh):
    JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/09_lora_finetune.py --quick

Args: lm.key=value / train.* overrides; --rank for the adapter rank;
--targets to choose adapted projections (comma list from
query,key,value,out,fc1,fc2).
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
from ddw_tpu.models.lora import count_trainable, lora_mask, merge_base_params
from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS
from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
from ddw_tpu.train.step import make_optimizer
from ddw_tpu.utils.compile_cache import enable_compile_cache
from ddw_tpu.utils.config import LMCfg, TrainCfg, apply_overrides


def successor_text(rng, n_seqs, seq_len, vocab, step):
    """Affine successor streams (the example-07 corpus) with a configurable
    step — pretrain on one step, adapt to another."""
    start = rng.randint(0, vocab, size=(n_seqs, 1))
    seq = (start + step * np.arange(seq_len + 1)[None, :]) % vocab
    noise = rng.rand(n_seqs, seq_len + 1) < 0.05
    seq = np.where(noise, rng.randint(0, vocab, size=seq.shape), seq)
    return seq.astype(np.int32)


def fit(step_fn, state, data, steps, batch_size, rngkey):
    """Returns (state, first_loss, last_loss) — the first step's loss is
    computed before any update applies, i.e. the zero-shot loss."""
    first = last = float("nan")
    for i in range(steps):
        # modular gather: constant [batch_size, seq] shape even when
        # batch_size does not divide len(data) (no mid-run recompile)
        idx = (np.arange(batch_size) + i * batch_size) % len(data)
        batch = data[idx]
        state, metrics = step_fn(state, batch[:, :-1], batch[:, 1:],
                                 jax.random.fold_in(rngkey, i))
        last = float(metrics["loss"])
        if i == 0:
            first = last
    return state, first, last


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true", help="tiny model + few steps")
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--targets", default="query,value")
    ap.add_argument("overrides", nargs="*", default=[])
    args = ap.parse_args()
    enable_compile_cache()

    cfgs = {"lm": LMCfg(vocab_size=64, max_len=128, hidden=64, depth=2,
                        num_heads=4, mlp_dim=128, dtype="float32"),
            "train": TrainCfg(batch_size=8, learning_rate=3e-3,
                              optimizer="adam", warmup_epochs=0)}
    apply_overrides(cfgs, args.overrides)
    lm_cfg, train_cfg = cfgs["lm"], cfgs["train"]
    seq = 32 if args.quick else min(lm_cfg.max_len, 128)
    pre_steps, ft_steps = (30, 40) if args.quick else (200, 200)

    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)))
    dp = len(jax.devices())
    # shard_map shards the batch P(data): round up to a mesh multiple (the
    # example-07 guard)
    train_cfg.batch_size = max(train_cfg.batch_size, dp) // dp * dp
    rng = np.random.RandomState(train_cfg.seed)

    # -- 1. pretrain the base LM on the step-1 successor process --------------
    base = build_lm(lm_cfg)
    tx = make_optimizer(train_cfg)
    state = init_lm_state(base, tx, jax.random.PRNGKey(train_cfg.seed))
    step_fn = make_lm_train_step(base, tx, mesh, DATA_AXIS, seq_axis=None)
    pre_data = successor_text(rng, 512, seq, lm_cfg.vocab_size, step=1)
    t0 = time.time()
    state, _, pre_loss = fit(step_fn, state, pre_data, pre_steps,
                             train_cfg.batch_size, jax.random.PRNGKey(1))
    print(f"pretrain: loss {pre_loss:.3f}  ({time.time() - t0:.1f}s)")

    # -- 2. LoRA-adapt to the step-3 process ----------------------------------
    import dataclasses

    lora_cfg = dataclasses.replace(
        lm_cfg, lora_rank=args.rank,
        lora_targets=tuple(args.targets.split(",")))
    tuned = build_lm(lora_cfg)
    ft_tx = make_optimizer(train_cfg)  # plain optax; lm_step applies the mask
    ft_state = init_lm_state(tuned, ft_tx, jax.random.PRNGKey(2))
    grafted = merge_base_params(ft_state.params, state.params)
    # host snapshot for the final frozen-base audit: the live tree's buffers
    # are donated into the first train step
    grafted_host = jax.device_get(grafted)
    ft_state = ft_state.replace(params=grafted)
    ft_step = make_lm_train_step(tuned, ft_tx, mesh, DATA_AXIS, seq_axis=None)
    ft_data = successor_text(rng, 512, seq, lm_cfg.vocab_size, step=3)

    trainable, total = count_trainable(grafted)
    print(f"adapters: rank {args.rank} on {args.targets} -> "
          f"{trainable}/{total} params train ({trainable / total:.1%})")

    # adapt; the first step's loss (pre-update) is the zero-shot loss on the
    # shifted task
    ft_state, zs_loss, ft_loss = fit(ft_step, ft_state, ft_data, ft_steps,
                                     train_cfg.batch_size,
                                     jax.random.PRNGKey(3))
    print(f"adapt: loss {zs_loss:.3f} -> {ft_loss:.3f}")

    # -- 3. the base stayed frozen -------------------------------------------
    mask = lora_mask(grafted_host)
    moved_frozen = jax.tree.leaves(jax.tree.map(
        lambda a, b, m: bool((np.asarray(a) != np.asarray(b)).any()) and not m,
        grafted_host, ft_state.params, mask))
    assert not any(moved_frozen), "frozen base parameters moved"
    print(f"final: adapt_loss={ft_loss:.3f} trainable_frac={trainable / total:.3f} "
          f"base_frozen=True")


if __name__ == "__main__":
    main()
