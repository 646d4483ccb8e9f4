"""End-to-end trainer tests on the 8-device CPU mesh: the minimum slice of
SURVEY §7 plus the distributed-DP contract (§2b) — learning happens, LR schedule
follows warmup/plateau, checkpoints resume, tracker records the run."""

import jax
import numpy as np
import pytest

from ddw_tpu.runtime.mesh import make_mesh, MeshSpec
from ddw_tpu.tracking.tracker import Tracker
from ddw_tpu.train.trainer import Trainer


def _mk_trainer(small_cfgs, silver, tmp_path, epochs=3, run=None, **overrides):
    data, model, train = small_cfgs
    for k, v in overrides.items():
        setattr(train, k, v)
    train.epochs = epochs
    mesh = make_mesh(MeshSpec((("data", 8),)))
    return Trainer(data, model, train, mesh=mesh, run=run)


def test_training_learns(small_cfgs, silver, tmp_path):
    train_tbl, val_tbl, _ = silver
    tr = _mk_trainer(small_cfgs, silver, tmp_path, epochs=4)
    res = tr.fit(train_tbl, val_tbl)
    assert res.epochs_run == 4
    # synthetic classes are separable: must beat 5-class chance clearly
    assert res.val_accuracy > 0.5, res.history
    assert res.history[-1]["loss"] < res.history[0]["loss"]


@pytest.mark.slow  # tier-1 budget (PR 18): the warmup-ramp shape keeps its
                   # tier-1 unit rep in test_ema_cosine::test_cosine_decay_
                   # shape; LR plumbing keeps test_lr_plumbing_through_ema_state.
def test_lr_warmup_schedule(small_cfgs, silver, tmp_path):
    """LR ramps to base*world over warmup_epochs (Goyal et al. scaling, reference
    03_model_training_distributed.py:314-318)."""
    train_tbl, val_tbl, _ = silver
    tr = _mk_trainer(small_cfgs, silver, tmp_path, epochs=3,
                     warmup_epochs=2, learning_rate=1e-3, scale_lr_by_world=True)
    res = tr.fit(train_tbl, val_tbl)
    lrs = [row["lr"] for row in res.history]
    world = 8
    assert lrs[0] < lrs[1] <= 1e-3 * world + 1e-9
    assert lrs[1] == pytest.approx(1e-3 * world, rel=1e-5)


@pytest.mark.slow   # tier-1 budget (PR 12): sync-resume keeps its
#                     bit-identity rep (test_resume.py
#                     test_resume_matches_uninterrupted) and the async
#                     writer keeps test_async_checkpoint_resume below;
#                     this epochs-continue bookkeeping sweep rides tier-2
def test_checkpoint_resume(small_cfgs, silver, tmp_path):
    train_tbl, val_tbl, _ = silver
    tr = _mk_trainer(small_cfgs, silver, tmp_path, epochs=2)
    res = tr.fit(train_tbl, val_tbl)
    steps_after_2 = int(jax.device_get(res.state.step))
    # resume continues instead of restarting
    tr2 = _mk_trainer(small_cfgs, silver, tmp_path, epochs=4)
    res2 = tr2.fit(train_tbl, val_tbl, resume=True)
    assert res2.epochs_run == 4
    assert int(jax.device_get(res2.state.step)) == 2 * steps_after_2


@pytest.mark.slow  # tier-1 budget (PR 18): async-writer semantics keep their
                   # tier-1 units in test_checkpoint.py (async==sync bytes,
                   # snapshot consistency, error surfacing); resume keeps
                   # test_resume + the sharded/zero resume reps.
def test_async_checkpoint_resume(small_cfgs, silver, tmp_path):
    """async_checkpoint=True: background writes are durable by fit()'s return
    (ckpt.wait barrier), and a resumed run continues from them."""
    train_tbl, val_tbl, _ = silver
    data, model, train = small_cfgs
    train.checkpoint_dir = str(tmp_path / "ackpt")
    tr = _mk_trainer((data, model, train), silver, tmp_path, epochs=2,
                     async_checkpoint=True)
    res = tr.fit(train_tbl, val_tbl)
    steps_after_2 = int(jax.device_get(res.state.step))
    from ddw_tpu.checkpoint.ckpt import latest_step

    assert latest_step(train.checkpoint_dir) == steps_after_2
    tr2 = _mk_trainer((data, model, train), silver, tmp_path, epochs=3,
                      async_checkpoint=True)
    res2 = tr2.fit(train_tbl, val_tbl, resume=True)
    assert int(jax.device_get(res2.state.step)) == steps_after_2 * 3 // 2


def test_tracker_records_run(small_cfgs, silver, tmp_path):
    train_tbl, val_tbl, _ = silver
    tracker = Tracker(str(tmp_path / "mlruns"), "exp")
    run = tracker.start_run("smoke")
    tr = _mk_trainer(small_cfgs, silver, tmp_path, epochs=2, run=run)
    tr.fit(train_tbl, val_tbl)
    run.end()
    got = tracker.get_run(run.run_id)
    assert got.meta()["status"] == "FINISHED"
    assert got.params()["train.batch_size"] == 8
    assert got.params()["world_size"] == 8
    hist = got.metric_history("val_accuracy")
    assert len(hist) == 2
    assert "images_per_sec" in got.final_metrics()


@pytest.mark.slow  # tier-1 budget (PR 16): the per-epoch callback path
#                    keeps tier-1 reps in test_early_stopping (epoch-end
#                    metric plumbing) + test_tracker_records_run (per-epoch
#                    records); this hook-contract sweep rides tier-2
def test_on_epoch_hook(small_cfgs, silver, tmp_path):
    """on_epoch sees each history row; returning True stops training — the
    HPO-pruner integration point (ddw_tpu.tune.pruner reports through it)."""
    train_tbl, val_tbl, _ = silver
    data, model, train = small_cfgs
    train.epochs = 5
    mesh = make_mesh(MeshSpec((("data", 8),)))

    seen = []

    def hook(row):
        seen.append(row["epoch"])
        assert "val_loss" in row
        return row["epoch"] >= 1

    res = Trainer(data, model, train, mesh=mesh, on_epoch=hook).fit(
        train_tbl, val_tbl)
    assert res.epochs_run == 2 and seen == [0, 1]

    # exceptions propagate out of fit (how Pruned aborts a trial)
    def bomb(row):
        raise RuntimeError("prune this trial")

    with pytest.raises(RuntimeError, match="prune this trial"):
        Trainer(data, model, train, mesh=mesh, on_epoch=bomb).fit(
            train_tbl, val_tbl)


# vision: ~17s; artifact-presence check (no numeric pin) — the vision
# trainer's profiler-trace drill rides the slow tier, the LM's (a few
# seconds) stays in tier-1
@pytest.mark.parametrize("kind", [
    pytest.param("vision", marks=pytest.mark.slow), "lm"])
def test_profiler_trace_writes_files(kind, small_cfgs, silver, tmp_path):
    """TrainCfg.trace_dir (Horovod-Timeline role): the first settled epoch
    runs under jax.profiler, a trace lands on disk, openable in
    TensorBoard/Perfetto, and the fit's own span tree beside it — the loop's
    doing, so whichever trainer was given the field."""
    import os

    from ddw_tpu.obs.trace import load_events

    trace_dir = str(tmp_path / "trace")
    if kind == "vision":
        train_tbl, val_tbl, _ = silver
        tr = _mk_trainer(small_cfgs, silver, tmp_path, epochs=2,
                         trace_dir=trace_dir)
        tr.fit(train_tbl, val_tbl)
    else:
        from ddw_tpu.data.prep import write_token_table
        from ddw_tpu.data.store import TableStore
        from ddw_tpu.train.lm_trainer import LMTrainer
        from ddw_tpu.utils.config import LMCfg, TrainCfg

        store = TableStore(str(tmp_path / "tok"))
        toks = np.random.RandomState(0).randint(0, 32, (40, 9)).astype(np.int32)
        lm = LMCfg(vocab_size=32, max_len=16, hidden=16, depth=1, num_heads=2,
                   mlp_dim=32, dropout=0.0, dtype="float32")
        tr = LMTrainer(lm, TrainCfg(batch_size=4, epochs=2, warmup_epochs=0,
                                    seed=0, num_devices=2,
                                    trace_dir=trace_dir))
        tr.fit_tables(write_token_table(store, "train", toks[:32], shard_size=8),
                      write_token_table(store, "val", toks[32:], shard_size=8))
    assert tr.tracer is None            # the fit's own, not left on the trainer
    found = [os.path.join(r, f) for r, _, fs in os.walk(trace_dir) for f in fs]
    assert any(f.endswith((".trace.json.gz", ".xplane.pb"))
               for f in found), found
    spans = load_events(os.path.join(trace_dir, "train_spans.trace.json"))
    epochs = [e for e in spans if e["name"] == "epoch"]
    assert [e["args"]["epoch"] for e in epochs] == [0, 1]   # 1 is the profiled
    assert {"fit_setup", "train_chain", "dispatch", "validation",
            "loader_batch"} <= {e["name"] for e in spans}


def test_early_stopping(small_cfgs, silver, tmp_path):
    train_tbl, val_tbl, _ = silver
    tr = _mk_trainer(small_cfgs, silver, tmp_path, epochs=10,
                     early_stop_patience=1, learning_rate=0.0)  # no learning => stop
    res = tr.fit(train_tbl, val_tbl)
    assert res.epochs_run < 10


def test_warmup_ramps_per_batch():
    """Horovod's LearningRateWarmupCallback ramps per *batch* (reference
    03_model_training_distributed.py:314-318); lr_for_step must be strictly
    increasing across batches inside the warmup window and hit base*world at
    the last warmup batch."""
    from ddw_tpu.train.callbacks import LRWarmup

    w = LRWarmup(base_lr=1e-3, world_size=8, warmup_epochs=2)
    steps = 5
    seq = [w.lr_for_step(e, s, steps) for e in range(3) for s in range(steps)]
    ramp, after = seq[: 2 * steps], seq[2 * steps:]
    assert all(b > a for a, b in zip(ramp, ramp[1:]))  # strictly increasing
    assert ramp[-1] == pytest.approx(8e-3)
    assert all(v == pytest.approx(8e-3) for v in after)
    # epoch-boundary values match the coarse schedule the history rows record
    assert w.lr_for_step(0, steps - 1, steps) == pytest.approx(w.lr_for_epoch(0))
    # world 1: no ramp, constant base
    w1 = LRWarmup(base_lr=1e-3, world_size=1, warmup_epochs=2)
    assert w1.lr_for_step(0, 0, steps) == pytest.approx(1e-3)


def test_keep_best_checkpoint(small_cfgs, silver, tmp_path):
    """checkpoint_keep_best (vision): <dir>/best holds the min-val_loss
    epoch's state with its metrics, independent of the resume stream."""
    from ddw_tpu.checkpoint.ckpt import CheckpointManager

    train_tbl, val_tbl, _ = silver
    ck = str(tmp_path / "ck_best")
    tr = _mk_trainer(small_cfgs, silver, tmp_path, epochs=3,
                     checkpoint_dir=ck, checkpoint_keep_best=True)
    res = tr.fit(train_tbl, val_tbl)
    meta = CheckpointManager(str(tmp_path / "ck_best" / "best")).read_metadata()
    assert meta["metrics"]["val_loss"] == pytest.approx(
        min(r["val_loss"] for r in res.history), abs=1e-6)
    assert "val_accuracy" in meta["metrics"]

    with pytest.raises(ValueError, match="checkpoint_dir"):
        _mk_trainer(small_cfgs, silver, tmp_path, epochs=1,
                    checkpoint_dir="", checkpoint_keep_best=True).fit(
            train_tbl, val_tbl)
