"""Family ``lm_train``: a decoder LM trained through ``LMTrainer.fit_tables``.

A configuration of this family is a GPT-2-style ``config.json`` (the keys of
``configs/gpt2-medium.json``); a traffic mix gives the sequence length, the
batch per chip, the steps per epoch and the optimizer's numbers.
"""

from __future__ import annotations

import math
import os
import types

from benchmark.harness.train_cell import run  # noqa: F401  (the family's runner)

# the step factory LMTrainer._run calls; harness/step_probe.py wraps it
STEP_FACTORY = ("ddw_tpu.train.lm_trainer", "make_lm_train_step")


# -- required operations ------------------------------------------------------
def matmul_params(config: dict) -> int:
    """Parameters that take part in a matrix product once per token: the
    blocks' projections and the output head. Embedding look-ups, biases and
    LayerNorm are left out."""
    d, f = config["n_embd"], config["n_inner"]
    per_block = 4 * d * d + 2 * d * f
    return config["n_layer"] * per_block + d * config["vocab_size"]


def required_flops_per_item(config: dict, seq: int | None = None) -> float:
    """Forward and backward FLOPs one token requires: 6 per matmul parameter
    (2 forward, 4 backward) plus attention's two S-long products per layer,
    ``12 * L * hidden * S``. Attention is counted FULL, not causal-halved:
    the program's XLA tiers compute the whole score matrix and mask it, so
    halving would flatter nothing and a kernel that skips the masked half
    shows as a gain. Recomputed work (the checkpointed attention tier,
    ``remat``) is not counted, so it earns nothing."""
    s = seq or config["n_positions"]
    return (6.0 * matmul_params(config)
            + 12.0 * config["n_layer"] * config["n_embd"] * s)


def loss_at_random(config: dict) -> float:
    return math.log(config["vocab_size"])


def hyper(traffic: dict) -> dict:
    return {"learning_rate": traffic["learning_rate"],
            "weight_decay": traffic["weight_decay"]}


# -- the reference, and how its leaves lie in the program's tree ---------------
def reference_spec(config: dict) -> dict:
    from benchmark.reference import gpt2

    return gpt2.weight_spec(config)


def reference_loss(config: dict, precision: str):
    from benchmark.reference import gpt2

    return gpt2.make_loss(config, precision)


def reference_batch(batch: tuple):
    import jax.numpy as jnp

    return jnp.asarray(batch[0], jnp.int32), jnp.asarray(batch[1], jnp.int32)


def reference_batch_shapes(config: dict, traffic: dict, sharding):
    import jax
    import jax.numpy as jnp

    toks = jax.ShapeDtypeStruct(
        (traffic["reference_micro_rows"], traffic["seq_len"]), jnp.int32,
        sharding=sharding)
    return toks, toks


_TOP = {("tok_embed", "embedding"): "wte", ("pos_embed",): "wpe",
        ("LayerNorm_0", "scale"): "lnf.g", ("LayerNorm_0", "bias"): "lnf.b",
        ("head", "kernel"): "head.w", ("head", "bias"): "head.b"}
_BLOCK = {("LayerNorm_0", "scale"): "ln1.g", ("LayerNorm_0", "bias"): "ln1.b",
          ("attn", "query", "kernel"): "attn.wq",
          ("attn", "query", "bias"): "attn.bq",
          ("attn", "key", "kernel"): "attn.wk",
          ("attn", "key", "bias"): "attn.bk",
          ("attn", "value", "kernel"): "attn.wv",
          ("attn", "value", "bias"): "attn.bv",
          ("attn", "out", "kernel"): "attn.wo",
          ("attn", "out", "bias"): "attn.bo",
          ("LayerNorm_1", "scale"): "ln2.g", ("LayerNorm_1", "bias"): "ln2.b",
          ("fc1", "kernel"): "fc1.w", ("fc1", "bias"): "fc1.b",
          ("fc2", "kernel"): "fc2.w", ("fc2", "bias"): "fc2.b"}


def leaf_map(config: dict) -> dict:
    """Program path -> reference key. The program's ``[hidden, heads, head]``
    projection kernels are the reference's ``[hidden, hidden]`` reshaped
    (heads are contiguous column blocks in both)."""
    out = dict(_TOP)
    for i in range(config["n_layer"]):
        for path, name in _BLOCK.items():
            out[(f"backbone_block{i}",) + path] = f"blk.{name}@{i}"
    return out


# -- the job ------------------------------------------------------------------
def make_corpus(seed: int, n_seqs: int, seq: int, vocab: int):
    """Arithmetic progressions modulo the vocabulary (``chip_smoke.py``'s
    corpus): every row differs, and there is structure to learn."""
    import numpy as np

    rng = np.random.RandomState(seed)
    start = rng.randint(0, vocab, (n_seqs, 1))
    stride = rng.randint(1, 5, (n_seqs, 1))
    return ((start + stride * np.arange(seq + 1)[None]) % vocab).astype(np.int32)


def _lm_cfg(config: dict, traffic: dict):
    from ddw_tpu.utils.config import LMCfg

    return LMCfg(vocab_size=config["vocab_size"],
                 max_len=config["n_positions"], hidden=config["n_embd"],
                 depth=config["n_layer"], num_heads=config["n_head"],
                 mlp_dim=config["n_inner"], dropout=config["resid_pdrop"],
                 dtype=config["dtype"], pos_encoding="learned",
                 remat=traffic["remat"])


def _train_cfg(traffic: dict, seed: int):
    from ddw_tpu.utils.config import TrainCfg

    return TrainCfg(
        batch_size=traffic["batch_per_chip"], epochs=10 ** 6,
        optimizer=traffic["optimizer"], learning_rate=traffic["learning_rate"],
        weight_decay=traffic["weight_decay"],
        scale_lr_by_world=traffic["scale_lr_by_world"],
        warmup_epochs=traffic["warmup_epochs"],
        lr_schedule=traffic["lr_schedule"],
        plateau_patience=traffic["plateau_patience"],
        steps_per_dispatch=traffic["steps_per_dispatch"], seed=seed)


def prepare(config: dict, traffic: dict, seed: int, work: str, devices: list):
    from ddw_tpu.data.prep import write_token_table
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_trainer import LMTrainer

    chips = len(devices)
    seq = traffic["seq_len"]
    global_batch = traffic["batch_per_chip"] * chips
    spe = traffic["steps_per_epoch"]
    corpus = make_corpus(seed, (spe + 1) * global_batch, seq,
                         config["vocab_size"])
    store = TableStore(os.path.join(work, "lm_tables"))
    train_tbl = write_token_table(store, "train", corpus[global_batch:])
    val_tbl = write_token_table(store, "val", corpus[:global_batch])

    lm_cfg = _lm_cfg(config, traffic)
    train_cfg = _train_cfg(traffic, seed)
    mesh = make_data_mesh(devices=devices)

    def fit(run, tracer):
        trainer = LMTrainer(lm_cfg, train_cfg, mesh=mesh, run=run,
                            tracer=tracer)
        return trainer.fit_tables(train_tbl, val_tbl)

    return types.SimpleNamespace(fit=fit, steps_per_epoch=spe,
                                 items_per_step=global_batch * seq)


def compile_step(config: dict, traffic: dict, devices: list):
    """Compile-only rehearsal: the trainer's step at the cell's full size for
    described devices (``rehearsal/compile_cells.py``). Nothing runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddw_tpu.models.lm import build_lm
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
    from ddw_tpu.train.step import make_optimizer

    lm_cfg = _lm_cfg(config, traffic)
    mesh = make_data_mesh(devices=devices)
    model = build_lm(lm_cfg)
    tx = make_optimizer(_train_cfg(traffic, 0))
    repl = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data"))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(lambda: init_lm_state(model, tx, jax.random.PRNGKey(0))))
    toks = jax.ShapeDtypeStruct(
        (traffic["batch_per_chip"] * len(devices), traffic["seq_len"]),
        jnp.int32, sharding=rows)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    step = make_lm_train_step(model, tx, mesh, seq_axis=None)
    return step.lower(state, toks, toks, key).compile()


def bare_step(config: dict, traffic: dict, devices: list):
    """The trainer's compiled step with a state and one batch, outside ``fit``
    (``tools/barrier_check.py`` only)."""
    import jax

    from ddw_tpu.models.lm import build_lm
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
    from ddw_tpu.train.step import make_optimizer

    lm_cfg = _lm_cfg(config, traffic)
    tx = make_optimizer(_train_cfg(traffic, 0))
    mesh = make_data_mesh(devices=devices)
    model = build_lm(lm_cfg)
    step = make_lm_train_step(model, tx, mesh, seq_axis=None)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0))
    rows = traffic["batch_per_chip"] * len(devices)
    corpus = make_corpus(0, rows, traffic["seq_len"], config["vocab_size"])
    batch = tuple(jax.device_put(x, step.batch_sharding)
                  for x in (corpus[:, :-1], corpus[:, 1:]))
    return step, state, batch


def tiny_batches(config: dict, traffic: dict, seed: int, steps: int) -> list:
    """Rows as the loader would feed them, without a trainer (the tests'
    lower-precision control)."""
    rows = traffic["batch_per_chip"]
    corpus = make_corpus(seed, rows * steps, traffic["seq_len"],
                         config["vocab_size"])
    return [(corpus[i * rows:(i + 1) * rows, :-1],
             corpus[i * rows:(i + 1) * rows, 1:]) for i in range(steps)]
