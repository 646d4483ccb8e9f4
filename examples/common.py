"""Shared example-script plumbing — the ``00_setup.py`` role.

The reference's setup notebook derives a per-user workspace and credentials
(``Part 1 - Distributed Training/00_setup.py:3-17``). Here: a single ``--workdir``
tree holds tables, runs, registry, checkpoints; ``--quick`` bootstraps the
zero-egress synthetic flowers dataset; ``section.key=value`` overrides come last.

Every example accepts:
    --workdir DIR     (default /tmp/ddw_tpu_workshop)
    --source DIR      raw JPEG class-dir tree (tf_flowers layout)
    --quick           synthetic data + SmallCNN + small images (CPU-friendly)
    overrides         e.g. train.batch_size=64 model.name=mobilenet_v2
"""

from __future__ import annotations

import argparse
import os

from ddw_tpu.data.prep import generate_synthetic_flowers
from ddw_tpu.data.store import TableStore
from ddw_tpu.tracking.registry import ModelRegistry
from ddw_tpu.tracking.tracker import Tracker
from ddw_tpu.utils.compile_cache import enable_compile_cache
from ddw_tpu.utils.config import DataCfg, ModelCfg, TrainCfg, TuneCfg, apply_overrides


def parse_args(description: str, extra=None):
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--workdir", default="/tmp/ddw_tpu_workshop")
    ap.add_argument("--source", default="", help="raw JPEG class-dir tree")
    ap.add_argument("--quick", action="store_true",
                    help="synthetic dataset + SmallCNN, small images")
    ap.add_argument("overrides", nargs="*", help="section.key=value config overrides")
    if extra:
        extra(ap)
    return ap.parse_args()


def setup(args) -> dict:
    """Build the config tree + workspace handles from CLI args."""
    enable_compile_cache()
    cfgs = {"data": DataCfg(), "model": ModelCfg(), "train": TrainCfg(), "tune": TuneCfg()}
    if args.quick:
        cfgs["data"].img_height = cfgs["data"].img_width = 32
        cfgs["data"].sample_fraction = 1.0
        cfgs["data"].shard_size = 32
        cfgs["model"].name = "small_cnn"
        cfgs["model"].dtype = "float32"
        cfgs["train"].batch_size = 8
        cfgs["train"].warmup_epochs = 0
    apply_overrides(cfgs, args.overrides)

    os.makedirs(args.workdir, exist_ok=True)
    source = args.source
    if not source:
        source = os.path.join(args.workdir, "raw_flowers")
        if not os.path.isdir(source):
            if not args.quick:
                raise SystemExit("--source required (or pass --quick for synthetic data)")
            print(f"[setup] generating synthetic flowers at {source}")
            generate_synthetic_flowers(source, images_per_class=40, size=48)
    cfgs["data"].source_dir = source
    cfgs["data"].table_root = os.path.join(args.workdir, "tables")

    return {
        "cfgs": cfgs,
        "store": TableStore(cfgs["data"].table_root),
        "tracker": Tracker(os.path.join(args.workdir, "runs"), "workshop"),
        "registry": ModelRegistry(os.path.join(args.workdir, "registry")),
        "workdir": args.workdir,
    }


def require_tables(store: TableStore, data_cfg=None):
    """Resolve the training tables. Prefers the pre-decoded ``*_decoded``
    tables (``01_data_prep.py --materialize``) when they exist AND match the
    configured image size — the decode-skip fast path — falling back to the
    JPEG silver tables otherwise."""
    if not (store.exists("silver_train") and store.exists("silver_val")):
        raise SystemExit("silver tables missing — run examples/01_data_prep.py first")
    train = store.table("silver_train")
    val = store.table("silver_val")
    return _prefer_materialized(store, data_cfg, train, val)


def ensure_frozen_backbone_cfg(model_cfg) -> None:
    """Demo-mode policy for the ``--cache-features`` examples: swap the
    backbone-less ``--quick`` default for a small frozen MobileNetV2 and opt
    into the frozen-random escape hatch when no pretrained artifact is set
    (one definition — examples 02 and 04 must not diverge)."""
    if model_cfg.name == "small_cnn":  # --quick default has no backbone/head split
        model_cfg.name, model_cfg.width_mult = "mobilenet_v2", 0.35
    model_cfg.freeze_base = True
    if not model_cfg.pretrained_path:
        model_cfg.allow_frozen_random = True  # demo without the ImageNet artifact


def _prefer_materialized(store, data_cfg, train, val):
    if (data_cfg is not None and store.exists("silver_train_decoded")
            and store.exists("silver_val_decoded")):
        t = store.table("silver_train_decoded")
        v = store.table("silver_val_decoded")
        size_ok = (t.meta.get("height"), t.meta.get("width")) == (
            data_cfg.img_height, data_cfg.img_width)
        # Freshness fence: the cache records which silver version it was
        # decoded from; after a re-prep (new silver version) a stale cache
        # must not silently win.
        fresh = (t.meta.get("source_version") == train.manifest["version"]
                 and v.meta.get("source_version") == val.manifest["version"])
        if size_ok and fresh:
            print("[tables] using pre-decoded raw_u8 tables (materialized cache)")
            return t, v
        if size_ok and not fresh:
            print("[tables] ignoring stale materialized cache (silver tables "
                  "are newer — re-run 01_data_prep.py --materialize)")
    return train, val
