"""Shared fixtures: synthetic flowers tree, prepared silver tables, small configs."""

import os

import numpy as np
import pytest

from ddw_tpu.data.prep import (generate_synthetic_flowers, prepare_flowers,
                               write_token_table)
from ddw_tpu.data.store import TableStore
from ddw_tpu.utils.config import DataCfg, ModelCfg, TrainCfg


@pytest.fixture(scope="session")
def flowers_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("flowers_src")
    return generate_synthetic_flowers(str(root), images_per_class=24, size=40)


@pytest.fixture(scope="session")
def store(tmp_path_factory):
    return TableStore(str(tmp_path_factory.mktemp("tables")))


@pytest.fixture(scope="session")
def silver(flowers_dir, store):
    """(train_table, val_table, label_to_idx) over the synthetic tree."""
    return prepare_flowers(flowers_dir, store, sample_fraction=1.0, shard_size=16)


@pytest.fixture(scope="module")
def token_tables(tmp_path_factory):
    """(train_table, val_table) of 17-token rows: 56 rows (three batches of 16
    and a remainder) and 16 (one validation batch)."""
    store = TableStore(str(tmp_path_factory.mktemp("tok")))
    toks = np.random.RandomState(0).randint(0, 32, (72, 17)).astype(np.int32)
    return (write_token_table(store, "train", toks[:56], shard_size=8),
            write_token_table(store, "val", toks[56:], shard_size=8))


@pytest.fixture()
def worker_pythonpath(monkeypatch):
    """Launcher workers import shipped fns by module name; put repo + tests on
    their path (used by the multi-process launcher/trainer tests)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    existing = os.environ.get("PYTHONPATH", "")
    parts = [repo, os.path.join(repo, "tests")] + ([existing] if existing else [])
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(parts))


@pytest.fixture()
def small_cfgs(tmp_path):
    data = DataCfg(img_height=32, img_width=32, shard_size=16, shuffle_buffer=64,
                   loader_workers=2)
    model = ModelCfg(name="small_cnn", num_classes=5, dropout=0.1, dtype="float32")
    train = TrainCfg(batch_size=8, epochs=2, learning_rate=1e-3, warmup_epochs=0,
                     seed=0, checkpoint_dir=str(tmp_path / "ckpt"))
    return data, model, train
