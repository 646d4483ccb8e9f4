"""The span tree of one ``fit``: both trainers at tiny size with a ``Tracer``
(names, nesting, counts, the loader's producer spans), and the same fits
without one — no event is built and the history is the same."""

import dataclasses
import gc
import re
import threading
import time

import numpy as np
import pytest

from ddw_tpu.obs import trace as trace_mod
from ddw_tpu.obs.trace import NULL_LANE, Tracer, span_lane
from ddw_tpu.runtime.mesh import MeshSpec, make_mesh
from ddw_tpu.train.lm_trainer import LMTrainer
from ddw_tpu.train.step import chain_plan
from ddw_tpu.train.trainer import Trainer
from ddw_tpu.utils.config import LMCfg, TrainCfg

EPOCHS = 2
SETUP = {"vision": {"model_init", "build_step", "place_state",
                    "build_loaders"},
         "lm": {"optimizer_init", "model_init", "build_step", "place_state",
                "build_loaders"}}
# spans every epoch has, by trainer
IN_EPOCH = {"vision": {"train_chain", "train_fetch", "validation",
                       "epoch_fetch", "epoch_report", "epoch_end"},
            "lm": {"train_chain", "validation", "epoch_fetch",
                   "epoch_report", "epoch_end"}}


def _fit(kind, tracer, small_cfgs, silver, token_tables, steps_per_dispatch=1):
    """One fit of EPOCHS epochs; returns (history, steps_per_epoch)."""
    if kind == "vision":
        data, model, train = small_cfgs
        train = dataclasses.replace(train, epochs=EPOCHS, checkpoint_dir="",
                                    steps_per_dispatch=steps_per_dispatch)
        mesh = make_mesh(MeshSpec((("data", 8),)))
        res = Trainer(data, model, train, mesh=mesh, tracer=tracer).fit(
            silver[0], silver[1])
        return res.history, silver[0].num_records // (8 * 8)
    lm = LMCfg(vocab_size=32, max_len=64, hidden=32, depth=2, num_heads=2,
               mlp_dim=64, dropout=0.0, dtype="float32")
    train = TrainCfg(batch_size=4, epochs=EPOCHS, warmup_epochs=0,
                     learning_rate=5e-3, seed=0, num_devices=4,
                     steps_per_dispatch=steps_per_dispatch)
    res = LMTrainer(lm, train, tracer=tracer).fit_tables(*token_tables)
    return res.history, token_tables[0].num_records // 16


def _wait_for_producers(timeout=10.0):
    """A dropped loader iterator stops its producer thread within one bounded
    put; wait for that, so no earlier fit's thread records into this test."""
    gc.collect()
    deadline = time.monotonic() + timeout
    alive = lambda: [t for t in threading.enumerate()
                     if "(producer)" in t.name]
    while alive() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not alive()


TOL = 2.0       # microseconds: stamps are doubles near 1.8e15 us


def _inside(child, parent):
    return (child["ts"] >= parent["ts"] - TOL and
            child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + TOL)


@pytest.mark.parametrize("kind,k", [("vision", 1), ("lm", 1), ("lm", 2)])
def test_fit_records_the_span_tree(kind, k, small_cfgs, silver, token_tables):
    tracer = Tracer(capacity=4096, process="t")
    history, spe = _fit(kind, tracer, small_cfgs, silver, token_tables, k)
    events = tracer.drain()
    assert tracer.spans_dropped == 0
    train = [e for e in events if e["tid"] == "train"]
    by_id = {e["span"]: e for e in train}
    named = lambda name, rows=train: [e for e in rows if e["name"] == name]

    # every child lies inside its parent, and every parent was recorded
    for e in train:
        if e["parent"] is not None:
            assert e["parent"] in by_id, e
            assert _inside(e, by_id[e["parent"]]), (e, by_id[e["parent"]])

    # set-up: one fit_setup that ends where the first train_chain starts
    (setup,) = named("fit_setup")
    kids = [e for e in train if e["parent"] == setup["span"]]
    assert {e["name"] for e in kids} == SETUP[kind]
    first_chain = min(named("train_chain"), key=lambda e: e["ts"])
    assert setup["ts"] + setup["dur"] == pytest.approx(first_chain["ts"],
                                                       abs=TOL)

    epochs = sorted(named("epoch"), key=lambda e: e["ts"])
    assert [e["args"]["epoch"] for e in epochs] == list(range(EPOCHS))
    plan = chain_plan(spe, k)
    for ep in epochs:
        assert ep["args"]["steps"] == spe
        kids = [e for e in train if e["parent"] == ep["span"]]
        assert {e["name"] for e in kids} == IN_EPOCH[kind]
        chains = named("train_chain", kids)
        assert len(chains) == len(plan)
        assert [c["args"]["k"] for c in sorted(chains, key=lambda e: e["ts"])
                ] == list(plan)
        for chain in chains:
            (wait,) = [e for e in named("data_wait")
                       if e["parent"] == chain["span"]]
            (disp,) = [e for e in named("dispatch")
                       if e["parent"] == chain["span"]]
            assert wait["dur"] + disp["dur"] <= chain["dur"] + TOL
            assert wait["ts"] + wait["dur"] <= disp["ts"] + TOL
            assert disp["args"]["k"] == chain["args"]["k"]
        (val,) = named("validation", kids)
        vkids = [e for e in train if e["parent"] == val["span"]]
        waits, disps = named("val_data_wait", vkids), named("val_dispatch",
                                                            vkids)
        assert len(waits) == len(disps) == val["args"]["steps"] >= 1
        assert [w["args"]["first"] for w in sorted(
            waits, key=lambda e: e["ts"])] == [True] + [False] * (len(waits) - 1)
        # the first wait starts with the validation span; the stream it
        # asks was opened after the epoch's first chain, and says for each
        # batch whether it waited in the queue
        assert min(w["ts"] for w in waits) == pytest.approx(val["ts"], abs=TOL)
        ready = [w["args"]["ready"] for w in waits]
        assert all(r in (True, False) for r in ready)
        assert ep["args"]["val_ready_share"] == sum(ready) / len(ready)
    assert not named("ckpt_save")           # no checkpoint_dir was given

    # the loaders' producers: a span a batch on their own thread lane
    loader = [e for e in events if e["tid"] == "loader"]
    assert {e["name"] for e in loader} >= {"loader_batch", "loader_h2d"}
    assert {e["name"] for e in loader} <= {"loader_batch", "loader_h2d",
                                           "loader_blocked"}
    # on that lane both producers: the training stream's batches, and each
    # epoch's validation pass, made while that epoch's chains ran — exactly
    # the one batch validation takes; the training stream may be ahead by its
    # queue, the batch in its hand, a chain being gathered and one in work
    made = named("loader_batch", loader)
    assert len(made) >= EPOCHS * (spe + 1)
    assert len(made) <= EPOCHS * (spe + 1) + (2 + 1) * k + k + 1
    assert len(named("loader_h2d", loader)) <= len(made)
    assert [r["val_ready_share"] for r in history] == [
        e["args"]["val_ready_share"] for e in epochs]
    assert len(history) == EPOCHS


@pytest.mark.parametrize("kind", ["vision", "lm"])
def test_without_a_tracer_no_event_is_built(kind, small_cfgs, silver,
                                            token_tables, monkeypatch):
    traced, _ = _fit(kind, Tracer(capacity=4096), small_cfgs, silver,
                     token_tables)
    _wait_for_producers()       # the traced fit's loader threads wind down
    built = []
    monkeypatch.setattr(trace_mod.Tracer, "_append",
                        lambda self, ev: built.append(ev))
    monkeypatch.setattr(trace_mod.Tracer, "_next_span_id",
                        lambda self: built.append("id") or "x")
    monkeypatch.setattr(trace_mod.SpanLane, "__init__",
                        lambda self, *a: built.append("lane"))
    plain, _ = _fit(kind, None, small_cfgs, silver, token_tables)
    assert built == []
    # what a row says of time is the run's own: the seconds, and whether the
    # validation batch already waited when a host this far ahead asked
    drop = lambda rows: [{k: v for k, v in r.items()
                          if k not in ("epoch_seconds", "images_per_sec",
                                       "val_ready_share")}
                         for r in rows]
    assert drop(plain) == drop(traced)


def test_checkpoint_save_is_a_child_of_epoch_end(tmp_path):
    lm = LMCfg(vocab_size=32, max_len=64, hidden=32, depth=1, num_heads=2,
               mlp_dim=64, dropout=0.0, dtype="float32")
    train = TrainCfg(batch_size=4, epochs=1, warmup_epochs=0, seed=0,
                     num_devices=2, checkpoint_dir=str(tmp_path / "ck"),
                     checkpoint_every_epochs=1)
    tracer = Tracer(capacity=1024)
    toks = np.random.RandomState(0).randint(0, 32, (24, 9)).astype(np.int32)
    LMTrainer(lm, train, tracer=tracer).fit(toks)
    events = tracer.drain()
    (save,) = [e for e in events if e["name"] == "ckpt_save"]
    (end,) = [e for e in events if e["name"] == "epoch_end"]
    assert save["parent"] == end["span"] and _inside(save, end)
    assert save["args"]["step"] > 0
    # fit() feeds no loader: nothing on the loader's lane
    assert not [e for e in events if e["tid"] == "loader"]


def test_null_lane_is_shared_and_inert():
    assert span_lane(None, "train", "train") is NULL_LANE
    assert NULL_LANE.on is False and NULL_LANE.open() is None
    assert NULL_LANE.span("x", 0.0, 1.0, None, None, None) is None
    tracer = Tracer(capacity=8)
    lane = span_lane(tracer, "train", "t0")
    parent = lane.open()
    lane.span("child", 1.0, 2.0, parent, args=lane.on and {"i": 1})
    lane.span("parent", 0.5, 2.5, span=parent)
    child, par = tracer.drain()
    assert (child["parent"], par["span"], child["tid"], child["cat"]) == (
        parent, parent, "t0", "train")
    assert child["args"] == {"i": 1} and par["args"] == {}


@pytest.mark.parametrize("kind", ["vision", "lm"])
def test_the_steps_carry_named_scopes(kind):
    """``fwd_bwd``, ``loss``, ``grad_sync`` and ``optimizer`` (and
    ``attention`` where the model has it) are on the operations of the lowered
    step, where a profile finds them; they are metadata and nothing else."""
    import jax

    mesh = make_mesh(MeshSpec((("data", 2),)), devices=jax.devices()[:2])
    key = jax.random.PRNGKey(0)
    if kind == "vision":
        from ddw_tpu.models.registry import build_model
        from ddw_tpu.train.step import init_state, make_train_step
        from ddw_tpu.utils.config import ModelCfg

        mcfg = ModelCfg(name="small_cnn", num_classes=5, dtype="float32")
        model = build_model(mcfg)
        state, tx = init_state(model, mcfg, TrainCfg(batch_size=2),
                               (16, 16, 3), key)
        step = make_train_step(model, tx, mesh, donate=False)
        batch = (np.zeros((4, 16, 16, 3), np.float32),
                 np.zeros((4,), np.int32))
        want = {"fwd_bwd", "loss", "grad_sync", "optimizer"}
    else:
        from ddw_tpu.models.lm import build_lm
        from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
        from ddw_tpu.train.step import make_optimizer

        model = build_lm(LMCfg(vocab_size=32, max_len=16, hidden=16, depth=1,
                               num_heads=2, mlp_dim=32, dtype="float32"))
        tx = make_optimizer(TrainCfg(batch_size=2))
        state = init_lm_state(model, tx, key, seq_len=8)
        step = make_lm_train_step(model, tx, mesh, seq_axis=None,
                                  donate=False)
        batch = (np.zeros((4, 8), np.int32), np.zeros((4, 8), np.int32))
        want = {"fwd_bwd", "loss", "grad_sync", "optimizer", "attention"}
    lowered = step.lower(state, *batch, key)
    text = lowered.as_text(debug_info=True)
    # under differentiation a scope reads jvp(loss) / transpose(jvp(loss))
    assert {s for s in want if re.search(rf'["/(]{s}[/)]', text)} == want
    assert "fwd_bwd" not in lowered.as_text()       # locations only
