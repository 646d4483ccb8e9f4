"""Family ``vision_train``: an image classifier fine-tuned through
``Trainer.fit`` over a JPEG table (decode, prefetch, data-parallel step).

A configuration of this family is a ViT-style ``config.json`` (the keys of
``configs/vit-b16.json``) plus ``registry_name``: the program's ``ModelCfg``
cannot set ViT's depth or widths, so the family registers the configuration's
sizes under that name through ``ddw_tpu.models.registry.register_model``, the
program's public extension point, and the trainer builds it from there.
"""

from __future__ import annotations

import math
import os
import types

from benchmark.harness.train_cell import run  # noqa: F401  (the family's runner)

# the step factory Trainer.fit calls; harness/step_probe.py wraps it
STEP_FACTORY = ("ddw_tpu.train.trainer", "make_train_step")
TABLE_CLASSES = 5       # data/prep.py's synthetic flowers


# -- required operations ------------------------------------------------------
def tokens(config: dict) -> int:
    return (config["image_size"] // config["patch_size"]) ** 2


def matmul_params_per_token(config: dict) -> int:
    d, f = config["hidden_size"], config["intermediate_size"]
    patch = config["patch_size"] ** 2 * config["num_channels"] * d
    return config["num_hidden_layers"] * (4 * d * d + 2 * d * f) + patch


def required_flops_per_item(config: dict) -> float:
    """Forward and backward FLOPs one image requires: 6 per matmul parameter
    per patch token (blocks and patch embedding), 6 per classifier parameter
    once, and attention's two S-long products, ``12 * L * hidden * S`` per
    token. Recomputed work is not counted."""
    s, d = tokens(config), config["hidden_size"]
    return (6.0 * s * matmul_params_per_token(config)
            + 6.0 * d * config["num_labels"]
            + 12.0 * config["num_hidden_layers"] * d * s * s)


def loss_at_random(config: dict) -> float:
    return math.log(config["num_labels"])


def hyper(traffic: dict) -> dict:
    return {"learning_rate": traffic["learning_rate"],
            "weight_decay": traffic["weight_decay"]}


# -- the reference, and how its leaves lie in the program's tree ---------------
def reference_spec(config: dict) -> dict:
    from benchmark.reference import vit

    return vit.weight_spec(config)


def reference_loss(config: dict, precision: str):
    from benchmark.reference import vit

    return vit.make_loss(config, precision)


def reference_batch(batch: tuple):
    import jax.numpy as jnp

    return jnp.asarray(batch[0], jnp.float32), jnp.asarray(batch[1], jnp.int32)


def reference_batch_shapes(config: dict, traffic: dict, sharding):
    import jax
    import jax.numpy as jnp

    n, size = traffic["reference_micro_rows"], config["image_size"]
    return (jax.ShapeDtypeStruct((n, size, size, config["num_channels"]),
                                 jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding))


_TOP = {("backbone_patch_embed", "kernel"): "patch.w",
        ("backbone_patch_embed", "bias"): "patch.b",
        ("pos_embed",): "pos",
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
          ("mlp", "fc1", "kernel"): "fc1.w", ("mlp", "fc1", "bias"): "fc1.b",
          ("mlp", "fc2", "kernel"): "fc2.w", ("mlp", "fc2", "bias"): "fc2.b"}


def leaf_map(config: dict) -> dict:
    """Program path -> reference key. The ``[p, p, channels, hidden]`` patch
    convolution is the reference's ``[p*p*channels, hidden]`` product over
    patches flattened in the same (row, column, channel) order."""
    out = dict(_TOP)
    for i in range(config["num_hidden_layers"]):
        for path, name in _BLOCK.items():
            out[(f"backbone_block{i}",) + path] = f"blk.{name}@{i}"
    return out


# -- the job ------------------------------------------------------------------
def register(config: dict) -> str:
    import jax.numpy as jnp

    from ddw_tpu.models.registry import register_model
    from ddw_tpu.models.vit import ViT

    name = config["registry_name"]

    @register_model(name)
    def _build(cfg):
        return ViT(num_classes=config["num_labels"],
                   patch=config["patch_size"], hidden=config["hidden_size"],
                   depth=config["num_hidden_layers"],
                   num_heads=config["num_attention_heads"],
                   mlp_dim=config["intermediate_size"],
                   dropout=config["hidden_dropout_prob"],
                   dtype=jnp.dtype(config["dtype"]))

    return name


def _configs(config: dict, traffic: dict, seed: int):
    from ddw_tpu.utils.config import DataCfg, ModelCfg, TrainCfg

    size = config["image_size"]
    data_cfg = DataCfg(img_height=size, img_width=size,
                       channels=config["num_channels"],
                       loader_workers=traffic["loader_workers"],
                       prefetch=traffic["prefetch"])
    model_cfg = ModelCfg(name=register(config), num_classes=TABLE_CLASSES,
                         dropout=config["hidden_dropout_prob"],
                         freeze_base=False, dtype=config["dtype"])
    train_cfg = TrainCfg(
        batch_size=traffic["batch_per_chip"], epochs=10 ** 6,
        optimizer=traffic["optimizer"], learning_rate=traffic["learning_rate"],
        weight_decay=traffic["weight_decay"],
        scale_lr_by_world=traffic["scale_lr_by_world"],
        warmup_epochs=traffic["warmup_epochs"],
        lr_schedule=traffic["lr_schedule"],
        plateau_patience=traffic["plateau_patience"],
        steps_per_dispatch=traffic["steps_per_dispatch"], seed=seed)
    return data_cfg, model_cfg, train_cfg


def prepare(config: dict, traffic: dict, seed: int, work: str, devices: list):
    from ddw_tpu.data.prep import generate_synthetic_flowers, prepare_flowers
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.native.decode import native_available
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.trainer import Trainer

    native_available()      # builds the decode library, or raises g++'s words
    global_batch = traffic["batch_per_chip"] * len(devices)
    spe = traffic["steps_per_epoch"]
    # spe training batches and one validation batch, two a class to spare so
    # that the seeded split's floor cannot come up one image short
    per_class = -(-(spe + 1) * global_batch // TABLE_CLASSES) + 2
    src = generate_synthetic_flowers(os.path.join(work, "flowers"),
                                     images_per_class=per_class,
                                     size=config["image_size"], seed=seed)
    store = TableStore(os.path.join(work, "tables"))
    train_tbl, val_tbl, _ = prepare_flowers(
        src, store, sample_fraction=1.0, train_fraction=spe / (spe + 1),
        split_seed=seed, shard_size=256)
    # the trainer takes its epoch from the table: floor(records / batch)
    spe = train_tbl.num_records // global_batch
    if spe < 1 or val_tbl.num_records < global_batch:
        raise RuntimeError(f"split {train_tbl.num_records} / "
                           f"{val_tbl.num_records} does not fill a training "
                           f"and a validation batch of {global_batch}")
    data_cfg, model_cfg, train_cfg = _configs(config, traffic, seed)
    mesh = make_data_mesh(devices=devices)

    def fit(run, tracer):
        trainer = Trainer(data_cfg, model_cfg, train_cfg, mesh=mesh, run=run,
                          tracer=tracer)
        return trainer.fit(train_tbl, val_tbl)

    return types.SimpleNamespace(fit=fit, steps_per_epoch=spe,
                                 items_per_step=global_batch)


def _model_and_state(config: dict, traffic: dict):
    import jax

    from ddw_tpu.models.registry import build_model
    from ddw_tpu.train.step import init_state

    data_cfg, model_cfg, train_cfg = _configs(config, traffic, 0)
    model = build_model(model_cfg)
    make_state = lambda: init_state(model, model_cfg, train_cfg,
                                    data_cfg.image_shape,
                                    jax.random.PRNGKey(0))
    return model, make_state


def compile_step(config: dict, traffic: dict, devices: list):
    """Compile-only rehearsal (``rehearsal/compile_cells.py``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.step import make_optimizer, make_train_step

    model, make_state = _model_and_state(config, traffic)
    mesh = make_data_mesh(devices=devices)
    repl, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    shapes = jax.eval_shape(lambda: make_state()[0])
    tx = make_optimizer(_configs(config, traffic, 0)[2])
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl), shapes)
    n, size = traffic["batch_per_chip"] * len(devices), config["image_size"]
    images = jax.ShapeDtypeStruct((n, size, size, config["num_channels"]),
                                  jnp.float32, sharding=rows)
    labels = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rows)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    step = make_train_step(model, tx, mesh)
    return step.lower(state, images, labels, key).compile()


def bare_step(config: dict, traffic: dict, devices: list):
    """The compiled step with a state and one batch, outside ``fit``
    (``tools/barrier_check.py`` only)."""
    import jax

    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.step import batch_sharding, make_train_step

    model, make_state = _model_and_state(config, traffic)
    state, tx = make_state()
    mesh = make_data_mesh(devices=devices)
    step = make_train_step(model, tx, mesh)
    global_rows = dict(traffic, batch_per_chip=traffic["batch_per_chip"]
                       * len(devices))
    batch = tuple(jax.device_put(x, batch_sharding(mesh))
                  for x in tiny_batches(config, global_rows, 0, 1)[0])
    return step, state, batch


def tiny_batches(config: dict, traffic: dict, seed: int, steps: int) -> list:
    """Rows shaped as the loader feeds them, without a trainer."""
    import numpy as np

    rng = np.random.RandomState(seed)
    n, size = traffic["batch_per_chip"], config["image_size"]
    return [(rng.uniform(-1, 1, (n, size, size, config["num_channels"])
                         ).astype(np.float32),
             rng.randint(0, TABLE_CLASSES, n).astype(np.int32))
            for _ in range(steps)]
