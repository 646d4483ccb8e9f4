"""The seam between the trainers and ``train/loop.py``: every kind of fit runs
its epochs through the one ``run_epochs``; a checkpoint's metadata and a
preemption are the same whichever trainer wrote or met them; and the step a
fit runs is one executable from its first call to its last."""

import dataclasses
import inspect
import re
import threading

import numpy as np
import pytest

from ddw_tpu.checkpoint.ckpt import CheckpointManager, latest_step
from ddw_tpu.obs.trace import Tracer
from ddw_tpu.parallel import pipeline, zero
from ddw_tpu.runtime import faults
from ddw_tpu.runtime.faults import Preempted
from ddw_tpu.runtime.mesh import MeshSpec, make_mesh
from ddw_tpu.train import lm_trainer, loop, trainer
from ddw_tpu.train.lm_trainer import LMTrainer
from ddw_tpu.train.step import chain_plan
from ddw_tpu.train.trainer import Trainer
from ddw_tpu.utils.config import LayerSpec, LMCfg, TrainCfg

SPE = 4         # steps an epoch, both trainers


def _tokens(n=36, seq=17):
    starts = np.random.RandomState(0).randint(0, 32, size=(n, 1))
    return ((starts + np.arange(seq)[None]) % 32).astype(np.int32)


def _fit(kind, small_cfgs, silver, *, run=None, tracer=None, resume=False,
         on_epoch=None, **train_kw):
    """One tiny fit of SPE steps an epoch: ``vision``, or the LM by its
    step (``lm``, ``lm-zero``, ``lm-fsdp``, ``lm-pp``), or (``lm-indexed``) an
    LM that attends to chosen keys at a length the Pallas kernels take."""
    if kind == "vision":
        data, model, train = small_cfgs
        mesh = make_mesh(MeshSpec((("data", 8),)))
        train = dataclasses.replace(train, **{
            "checkpoint_dir": "",
            "batch_size": silver[0].num_records // (8 * SPE), **train_kw})
        return Trainer(data, model, train, mesh=mesh, run=run, tracer=tracer,
                       on_epoch=on_epoch).fit(silver[0], silver[1],
                                              resume=resume)
    lm = LMCfg(vocab_size=32, max_len=16, hidden=16, num_heads=2, mlp_dim=32,
               depth=2 if kind == "lm-pp" else 1, dropout=0.0,
               dtype="float32")
    seq = 17
    if kind == "lm-indexed":
        seq = 513
        lm = dataclasses.replace(
            lm, max_len=512, hidden=64, num_kv_heads=1, pos_encoding="rope",
            remat="full", layer=LayerSpec(
                norm="rmsnorm", bias=False, head_dim=64, attention="indexed",
                index_heads=2, index_head_dim=8, index_topk=128,
                index_tile=128, mlp="swiglu"))
    extra = {"lm-zero": {"zero": True}, "lm-fsdp": {"fsdp": True},
             "lm-pp": {"pipeline_stages": 2, "pipeline_microbatches": 2},
             "lm": {}, "lm-indexed": {}}[kind]
    train = TrainCfg(batch_size=4, epochs=2, warmup_epochs=0, seed=0,
                     learning_rate=1e-2, num_devices=2, **extra)
    train = dataclasses.replace(train, **train_kw)
    if kind == "lm-pp":     # the batch is over 'data' alone: 1 of 2 devices
        train = dataclasses.replace(train, batch_size=8)
    # 36 sequences: 4 held out, 32 = SPE batches of 8
    return LMTrainer(lm, train, run=run, tracer=tracer).fit(
        _tokens(seq=seq), val_fraction=0.1, resume=resume)


@pytest.fixture()
def loop_calls(monkeypatch):
    """The keyword arguments of every ``run_epochs`` call of the test."""
    calls, run_epochs = [], loop.run_epochs

    def counted(**kw):
        calls.append(kw)
        return run_epochs(**kw)

    monkeypatch.setattr(loop, "run_epochs", counted)
    return calls


# the plain steps, chained and not, are the cases of the two tests below
@pytest.mark.parametrize("kind,k", [("vision", 2), ("lm-zero", 1),
                                    ("lm-fsdp", 2), ("lm-pp", 1)])
def test_every_fit_runs_the_one_loop(kind, k, small_cfgs, silver, loop_calls):
    res = _fit(kind, small_cfgs, silver, steps_per_dispatch=k)
    (kw,) = loop_calls
    assert res.epochs_run == 2 and len(res.history) == 2
    assert list(kw["plan"]) == list(chain_plan(SPE, k))
    assert all(np.isfinite(r["loss"]) and np.isfinite(r["val_loss"])
               for r in res.history)
    # what only one trainer reports rides on the one row
    assert ("epoch_seconds" in res.history[0]) == (kind == "vision")
    assert ("pp_bubble_fraction" in res.history[0]) == (kind == "lm-pp")


def test_the_loop_lives_in_one_file():
    """The hooks, the fetch, the span names and the profiler's start are the
    loop's alone; the LM trainer does not import from its sibling."""
    sources = {m.__name__: inspect.getsource(m)
               for m in (trainer, lm_trainer, loop)}
    for needle in ("maybe_fault(", "preemption_requested(",
                   "maybe_elastic_restart(", "fetch_metrics_mean(",
                   '"train_chain"', '"val_data_wait"', '"epoch_fetch"',
                   '"ckpt_save"', "jax.profiler.start_trace"):
        assert [n for n, s in sources.items() if needle in s] == [
            loop.__name__], needle
    assert not re.search(r"^\s*(from|import) ddw_tpu\.train\.trainer",
                         sources[lm_trainer.__name__], re.M)


class _Metrics:
    """A tracker run that keeps the rows it is sent."""

    def __init__(self, after_row=lambda: None):
        self.rows, self.after_row = [], after_row

    def log_params(self, params):
        pass

    def log_metrics(self, row, step=None):
        self.rows.append((step, dict(row)))
        self.after_row()


@pytest.mark.parametrize("kind", ["vision", "lm"])
def test_checkpoint_metadata_is_the_union(kind, small_cfgs, silver, tmp_path,
                                          loop_calls):
    ck = str(tmp_path / "ck")
    run = _Metrics()
    res = _fit(kind, small_cfgs, silver, run=run, checkpoint_dir=ck,
               checkpoint_every_epochs=1)
    assert len(loop_calls) == 1
    assert latest_step(ck) == 2 * SPE           # saved under the host step
    meta = CheckpointManager(ck).read_metadata()
    assert meta["epoch"] == 1 and "plateau" in meta["callbacks"]
    assert meta["metrics"] == res.history[-1]
    assert meta["val_loss"] == res.history[-1]["val_loss"]
    assert meta["val_accuracy"] == res.history[-1]["val_accuracy"]
    # the report: once an epoch, the row less its number
    assert [step for step, _ in run.rows] == [0, 1]
    assert run.rows[-1][1] == {k: v for k, v in res.history[-1].items()
                               if k != "epoch"}


@pytest.mark.parametrize("kind,k", [("vision", 1), ("lm", 1), ("lm", 2)])
def test_preemption_leaves_at_the_next_chain_boundary(kind, k, small_cfgs,
                                                      silver, tmp_path,
                                                      loop_calls):
    """Asked for while epoch 0 is reported, the stop comes at epoch 1's first
    chain, before its batch is asked for: a checkpoint under the host step,
    ``Preempted`` carrying it, and no writer left behind."""
    ck = str(tmp_path / "ck")
    tracer = Tracer(capacity=4096)
    try:
        with pytest.raises(Preempted) as exc:
            _fit(kind, small_cfgs, silver, tracer=tracer,
                 run=_Metrics(after_row=faults.request_preemption),
                 steps_per_dispatch=k, checkpoint_dir=ck,
                 checkpoint_every_epochs=2, async_checkpoint=True)
    finally:
        faults.reset_preemption()
    assert exc.value.step == SPE and len(loop_calls) == 1
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ckpt-writer")]
    assert latest_step(ck) == SPE
    meta = CheckpointManager(ck).read_metadata()
    assert meta["preempted"] is True and meta["epoch"] == 1
    events = [e for e in tracer.drain() if e["tid"] == "train"]
    named = lambda name: [e for e in events if e["name"] == name]
    assert len(named("data_wait")) == len(chain_plan(SPE, k))  # epoch 0's
    (save,) = named("ckpt_save")
    assert save["args"]["step"] == SPE
    assert [e["args"]["epoch"] for e in named("epoch")] == [0]


# -- one executable of the step a fit -----------------------------------------
STEP_FACTORIES = [(trainer, "make_train_step"), (trainer, "make_train_chain"),
                  (lm_trainer, "make_lm_train_step"),
                  (lm_trainer, "make_lm_train_chain"),
                  (zero, "make_zero_train_step"),
                  (zero, "make_zero_train_chain"),
                  (zero, "make_fsdp_train_step"),
                  (zero, "make_fsdp_train_chain"),
                  (pipeline, "make_pp_lm_train_step")]


class _Seen:
    """Stands where a step stands, as the benchmark's probe does
    (``benchmark/harness/step_probe.py``): calls and attributes go through."""

    def __init__(self, inner):
        self._inner, self.calls = inner, 0

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __call__(self, *args):
        self.calls += 1
        return self._inner(*args)


@pytest.fixture()
def steps_made(monkeypatch):
    """Every step and chain the trainers' factories make in the test."""
    made = []
    for module, name in STEP_FACTORIES:
        def wrapped(*a, _factory=getattr(module, name), **kw):
            made.append(_Seen(_factory(*a, **kw)))
            return made[-1]

        monkeypatch.setattr(module, name, wrapped)
    return made


def _one_executable(steps_made, tracer, epochs):
    """The step that ran holds one executable, every ``epoch`` span says so,
    and the fit placed its state once, inside ``fit_setup``."""
    (run_step,) = [s for s in steps_made if s.calls]
    assert run_step._cache_size() == 1
    events = [e for e in tracer.drain() if e["tid"] == "train"]
    named = lambda name: [e for e in events if e["name"] == name]
    assert [(e["args"]["epoch"], e["args"]["step_variants"])
            for e in named("epoch")] == [(i, 1) for i in epochs]
    (placed,), (setup,) = named("place_state"), named("fit_setup")
    assert placed["parent"] == setup["span"]
    return placed["args"]


@pytest.mark.parametrize("kind,k", [("vision", 1), ("vision", 2), ("lm", 1),
                                    ("lm", 2), ("lm-zero", 1),
                                    ("lm-fsdp", 2), ("lm-pp", 1)])
def test_a_fit_builds_one_executable_of_its_step(kind, k, small_cfgs, silver,
                                                 steps_made):
    tracer = Tracer(capacity=4096)
    _fit(kind, small_cfgs, silver, tracer=tracer, steps_per_dispatch=k)
    placed = _one_executable(steps_made, tracer, [0, 1])
    # a fresh state: every leaf had to be placed
    assert placed["leaves"] > 0 and placed["bytes"] > 0


def test_the_indexed_step_is_one_executable_with_the_kernels_in_it(
        small_cfgs, silver, steps_made, monkeypatch):
    """A layer that attends to chosen keys takes the Pallas kernels from 512
    tokens (``ops/indexed_attention.py`` reads the shapes), and the fit still
    builds its step once."""
    from ddw_tpu.ops import indexed_kernels

    calls, attend = [], indexed_kernels.attend_chosen
    monkeypatch.setattr(
        indexed_kernels, "attend_chosen",
        lambda q, *a, **kw: calls.append(q.shape) or attend(q, *a, **kw))
    tracer = Tracer(capacity=4096)
    _fit("lm-indexed", small_cfgs, silver, tracer=tracer)
    _one_executable(steps_made, tracer, [0, 1])
    assert calls and {shape[1:] for shape in calls} == {(512, 2, 64)}


@pytest.mark.parametrize("kind", ["vision", "lm"])
def test_the_schedule_leaves_the_placement_alone(kind, small_cfgs, silver,
                                                 steps_made):
    """More than one device: the warmup writes the rate before every batch of
    epoch 0, the plateau regime takes over, and (the rate being 0, no epoch
    improves on the one before) a cut comes at epoch 1's end."""
    tracer = Tracer(capacity=4096)
    res = _fit(kind, small_cfgs, silver, tracer=tracer, epochs=3,
               warmup_epochs=1, plateau_patience=1, learning_rate=0.0)
    assert [r["lr"] for r in res.history] == [0.0, 0.0,
                                              pytest.approx(1e-7)]
    _one_executable(steps_made, tracer, [0, 1, 2])


@pytest.mark.parametrize("kind", ["vision", "lm", "lm-zero"])
def test_a_resumed_fit_builds_one_executable(kind, small_cfgs, silver,
                                             steps_made, tmp_path):
    kw = {"checkpoint_dir": str(tmp_path / "ck"), "checkpoint_every_epochs": 1}
    _fit(kind, small_cfgs, silver, epochs=1, **kw)
    del steps_made[:]
    tracer = Tracer(capacity=4096)
    res = _fit(kind, small_cfgs, silver, tracer=tracer, resume=True, epochs=3,
               **kw)
    assert [r["epoch"] for r in res.history] == [1, 2]
    _one_executable(steps_made, tracer, [1, 2])


# -- the epoch's validation stream is opened ahead ------------------------------
def _fit_loaders(kind, small_cfgs, silver, token_tables, **kw):
    """A fit whose validation batches come from a loader: ``vision`` as in
    ``_fit``, ``lm-tables`` the LM through ``fit_tables`` (3 steps an
    epoch)."""
    if kind == "vision":
        return _fit(kind, small_cfgs, silver, **kw)
    lm = LMCfg(vocab_size=32, max_len=16, hidden=16, num_heads=2, mlp_dim=32,
               depth=1, dropout=0.0, dtype="float32")
    run, tracer, resume = (kw.pop(k, d) for k, d in (
        ("run", None), ("tracer", None), ("resume", False)))
    train = TrainCfg(**{"batch_size": 4, "epochs": 2, "warmup_epochs": 0,
                        "seed": 0, "learning_rate": 1e-2, "num_devices": 4,
                        **kw})
    return LMTrainer(lm, train, run=run, tracer=tracer).fit_tables(
        *token_tables, resume=resume)


@pytest.fixture()
def val_streams(monkeypatch):
    """Every validation pass the test's fits open (the loaders with
    ``num_batches``): ``(loader, stream, batches)``, the batches as host
    arrays in the order the loop took them."""
    from ddw_tpu.data import loader as loader_mod

    opened, open_ = [], loader_mod.ShardedLoader.open

    class Kept:
        """Stands where the stream stands and keeps what it hands out."""

        def __init__(self, inner, taken):
            self._inner, self._taken = inner, taken

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def __next__(self):
            batch = next(self._inner)
            self._taken.append(tuple(np.asarray(x) for x in batch))
            return batch

    def kept_open(self):
        stream = open_(self)
        if self.num_batches is None:
            return stream
        opened.append((self, stream, []))
        return Kept(stream, opened[-1][2])

    monkeypatch.setattr(loader_mod.ShardedLoader, "open", kept_open)
    return opened


@pytest.mark.parametrize("kind", ["vision", "lm-tables"])
def test_the_validation_stream_is_at_work_while_the_epoch_trains(
        kind, small_cfgs, silver, token_tables, val_streams, monkeypatch):
    """A slow decode (30 ms a validation batch) under a slower epoch (40 ms a
    chain): every epoch's pass is begun between the dispatch of the epoch's
    first chain and that of its last, exactly ``val_steps`` batches of it, and
    when validation asks
    every batch waits in the queue — ``val_ready_share`` 1.0 on the row, the
    ``epoch`` span and each ``val_data_wait``."""
    import time

    from ddw_tpu.data.loader import ShardedLoader

    log, host_batches = [], ShardedLoader._iter_batches

    def slow_decode(self):
        for batch in host_batches(self):
            if self.num_batches is not None:
                log.append("val_batch")
                time.sleep(0.03)
            yield batch

    def slow_chain(kind_, step, **kw):
        log.append(step)
        time.sleep(0.04)

    monkeypatch.setattr(ShardedLoader, "_iter_batches", slow_decode)
    monkeypatch.setattr(loop, "maybe_fault", slow_chain)
    tracer = Tracer(capacity=4096)
    res = _fit_loaders(kind, small_cfgs, silver, token_tables, tracer=tracer)
    spe = SPE if kind == "vision" else 3
    assert [r["val_ready_share"] for r in res.history] == [1.0, 1.0]
    for _, stream, batches in val_streams:
        assert len(batches) == 1 and not stream._thread.is_alive()
    assert len(val_streams) == 2 and log.count("val_batch") == 2
    # a chain's mark is made before its dispatch: the pass is begun after
    # the epoch's first chain and before its last is dispatched
    chains = [i for i, e in enumerate(log) if e != "val_batch"]
    made = [i for i, e in enumerate(log) if e == "val_batch"]
    assert [log[i] for i in chains] == list(range(2 * spe))
    assert chains[0] < made[0] < chains[spe - 1]
    assert chains[spe] < made[1] < chains[-1]
    events = [e for e in tracer.drain() if e["tid"] == "train"]
    assert [e["args"]["val_ready_share"] for e in events
            if e["name"] == "epoch"] == [1.0, 1.0]
    assert [e["args"]["ready"] for e in events
            if e["name"] == "val_data_wait"] == [True, True]


@pytest.mark.parametrize("kind", ["vision", "lm-tables"])
def test_every_epoch_validates_a_fresh_pass_from_the_tables_start(
        kind, small_cfgs, silver, token_tables, val_streams, tmp_path):
    """Epochs 0 and 1 of one fit, and epochs 1 and 2 of a resumed one: the
    batches validation took are the leading ``val_steps`` batches of an
    unshuffled pass over the validation table, the same each time."""
    from ddw_tpu.data.loader import ShardedLoader

    kw = {"checkpoint_dir": str(tmp_path / "ck"), "checkpoint_every_epochs": 1}
    _fit_loaders(kind, small_cfgs, silver, token_tables, epochs=2, **kw)
    res = _fit_loaders(kind, small_cfgs, silver, token_tables, epochs=4,
                       resume=True, **kw)
    assert [r["epoch"] for r in res.history] == [2, 3]
    assert len(val_streams) == 4
    if kind == "vision":
        fresh = ShardedLoader(
            silver[1], batch_size=8 * (silver[0].num_records // (8 * SPE)),
            image_size=(32, 32), shuffle=False, num_epochs=None, workers=2)
    else:
        fresh = ShardedLoader(token_tables[1], batch_size=16, shuffle=False,
                              num_epochs=1)
    want = next(iter(fresh))
    for loader, _, batches in val_streams:
        assert loader.shuffle is False and loader.skip_records == 0
        assert len(batches) == loader.num_batches == 1
        for got, ref in zip(batches[0], want):
            np.testing.assert_array_equal(got, ref)


def _raise_in_step(monkeypatch):
    """The vision step raises at its third call."""
    make = trainer.make_train_step

    def made(*a, **kw):
        step, calls = make(*a, **kw), []

        class Step(_Seen):
            def __call__(self, *args):
                calls.append(1)
                if len(calls) == 3:
                    raise FloatingPointError("step 3")
                return self._inner(*args)

        return Step(step)

    monkeypatch.setattr(trainer, "make_train_step", made)


@pytest.mark.parametrize("way", ["preempted", "on_epoch", "step_raises"])
def test_no_validation_stream_outlives_its_epoch(way, small_cfgs, silver,
                                                 token_tables, val_streams,
                                                 monkeypatch):
    """Whichever way the fit leaves — ``Preempted`` at epoch 1's second
    chain, an ``on_epoch`` that stops after epoch 0, an exception in epoch 0's
    third step — every validation stream it opened is closed: producer
    thread gone, queue empty."""
    if way == "preempted":
        # asked for at the mark of epoch 1's second chain, whose check meets
        # it at once: epoch 1's stream is open and nobody has asked it
        fault = loop.maybe_fault
        monkeypatch.setattr(loop, "maybe_fault", lambda kind, step, **kw: (
            step == SPE + 1 and faults.request_preemption(),
            fault(kind, step=step, **kw)))
        try:
            with pytest.raises(Preempted) as exc:
                _fit_loaders("vision", small_cfgs, silver, token_tables)
        finally:
            faults.reset_preemption()
        assert exc.value.step == SPE + 1
        opened = 2          # epoch 0's, consumed; epoch 1's, never asked
    elif way == "on_epoch":
        res = _fit_loaders("vision", small_cfgs, silver, token_tables,
                           on_epoch=lambda row: True)
        assert res.epochs_run == 1
        opened = 1
    else:
        _raise_in_step(monkeypatch)
        with pytest.raises(FloatingPointError):
            _fit_loaders("vision", small_cfgs, silver, token_tables)
        opened = 1
    assert len(val_streams) == opened
    for _, stream, _ in val_streams:
        assert not stream._thread.is_alive() and stream._q.empty()
        with pytest.raises(StopIteration):
            next(stream)
    # taken: epoch 0's one batch, unless the fit left before its validation
    assert [len(b) for _, _, b in val_streams] == {
        "preempted": [1, 0], "on_epoch": [1], "step_raises": [0]}[way]


@pytest.mark.parametrize("kind", ["vision", "lm-tables"])
def test_an_epoch_without_a_chain_opens_its_stream_at_validation(
        kind, small_cfgs, silver, token_tables, val_streams, monkeypatch):
    """The loop tells a loader's provider by what it is at validation too: an
    epoch that dispatches no chain never reached the place the stream is
    opened ahead, so it is opened at the ask (a loader is not callable), and
    read, counted and closed like any other."""
    run_epochs = loop.run_epochs
    monkeypatch.setattr(loop, "run_epochs",
                        lambda **kw: run_epochs(**{**kw, "plan": []}))
    res = _fit_loaders(kind, small_cfgs, silver, token_tables, epochs=1)
    assert [len(b) for _, _, b in val_streams] == [1]
    assert not val_streams[0][1]._thread.is_alive()
    row, = res.history
    assert "val_ready_share" in row and np.isfinite(row["val_loss"])


def test_a_provider_without_a_loader_puts_no_counter_on_the_row(small_cfgs,
                                                                silver,
                                                                val_streams):
    """``LMTrainer.fit`` over arrays in memory: nothing to open, the provider
    is asked at validation as before; no ``val_ready_share``, no ``ready``."""
    tracer = Tracer(capacity=4096)
    res = _fit("lm", small_cfgs, silver, tracer=tracer)
    assert not val_streams
    assert all("val_ready_share" not in r for r in res.history)
    events = [e for e in tracer.drain() if e["tid"] == "train"]
    waits = [e for e in events if e["name"] == "val_data_wait"]
    assert waits and all("ready" not in e["args"] for e in waits)
    assert all("val_ready_share" not in e["args"] for e in events
               if e["name"] == "epoch")
