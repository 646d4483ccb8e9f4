"""The ``step_scopes`` table (``ddw_tpu/obs/step_scopes.py``): read from the
compiled step of each of the benchmark's four models at tiny sizes it names
every scope the model opens, flags a rematerialised block's recomputation and
lists a scan's body under its ``while``; the trainers' loop records it once a
traced fit, as the ``step_scopes`` span, and builds nothing without a tracer;
the span survives the Chrome trace that ``TrainCfg.trace_dir`` writes."""

import json

import jax
import numpy as np
import pytest

from benchmark.harness.manifest import Cell, load_manifest
from benchmark.tests import tiny
from ddw_tpu.obs import step_scopes
from ddw_tpu.obs.step_scopes import parse_hlo, scope_path
from ddw_tpu.obs.trace import Tracer, chrome_trace, load_events
from ddw_tpu.train import loop
from ddw_tpu.train.lm_trainer import LMTrainer
from ddw_tpu.utils.config import LMCfg, TrainCfg

STEP = {"fwd_bwd", "loss", "optimizer", "grad_sync"}
DENSE = STEP | {"embed", "attn_proj", "attention", "mlp", "head"}
# cell -> (remat, the scopes its model and step open)
CASES = {
    "gpt2m_train_s1024": ("full", DENSE),
    "vitb16_train_224": (None, DENSE),
    "keyevl2_train_s8192": ("full", STEP | {
        "embed", "attn_proj", "indexer", "key_select", "sparse_attention",
        "router", "experts", "head"}),
    "nemotron3nano_train_s8192": ("full", STEP | {
        "embed", "attn_proj", "attention", "ssm_proj", "ssm_conv", "ssm_scan",
        "ssm_gate_norm", "router", "experts", "shared_expert", "head"}),
}


def _tiny_of(family) -> dict:
    return tiny.TINY.get(family.__name__.rpartition(".")[2]) or family.TINY


@pytest.fixture(scope="module")
def tables():
    """The table of each cell's step at its family's tiny sizes, two CPU
    devices (so that the gradient is reduced), made once."""
    made = {}

    def table(cell_name):
        if cell_name not in made:
            cell = Cell(load_manifest(), cell_name)
            sizes = _tiny_of(cell.family)
            remat = CASES[cell_name][0]
            traffic = {**cell.traffic, **sizes["traffic"],
                       **({"remat": remat} if remat else {})}
            compiled = cell.family.compile_step(
                dict(cell.config, **sizes["config"]), traffic,
                jax.devices()[:2])
            made[cell_name] = parse_hlo(compiled.as_text())
        return made[cell_name]

    return table


def _names(table) -> set:
    return {part for path in table["scopes"] for part in path.split("/")[1:]}


@pytest.mark.parametrize("cell", sorted(CASES))
def test_the_table_names_every_scope_of_the_model(cell, tables):
    table = tables(cell)
    # the name the device trace gives the step's executions
    assert table["module"] == ("jit__dp_step_body" if cell.startswith("vit")
                               else "jit__step")
    assert CASES[cell][1] <= _names(table)
    # every operation has a place in the list or is counted as unnamed
    assert all(-1 <= i < len(table["scopes"]) for i in table["ops"].values())
    assert table["unnamed_ops"] == sum(i < 0 for i in table["ops"].values())
    assert 0 < table["unnamed_ops"] < len(table["ops"])
    # compact: a path is kept once, bookkeeping is left out
    assert len(set(table["scopes"])) == len(table["scopes"])
    assert not any(name.startswith(("parameter", "get-tuple-element"))
                   for name in table["ops"])
    json.dumps(table)       # plain data: it is a span's arguments


@pytest.mark.parametrize("cell", [c for c, (remat, _) in sorted(CASES.items())
                                  if remat])
def test_a_rematted_blocks_recomputation_is_flagged(cell, tables):
    by_pass = {}
    for path in tables(cell)["scopes"]:
        which, *parts = path.split("/")
        by_pass.setdefault(which, set()).update(parts)
    assert set(by_pass) == {"fwd", "bwd", "remat"}
    # the block's layers run again in the backward pass; the step's own
    # scopes and what lies outside the blocks do not
    assert "attn_proj" in by_pass["remat"]
    assert not by_pass["remat"] & {"optimizer", "loss", "head", "embed",
                                   "grad_sync"}
    assert "optimizer" not in by_pass["bwd"]


def test_a_scans_body_is_listed_under_its_while(tables):
    table = tables("nemotron3nano_train_s8192")
    scopes, ops = table["scopes"], table["ops"]

    def scan(name):
        return ops[name] >= 0 and "ssm_scan" in scopes[ops[name]].split("/")

    loops = {name: body for name, body in table["inside"].items()
             if name.startswith("while") and scan(name)}
    assert loops
    for name, body in loops.items():
        assert body and all(b in ops for b in body)
        assert any(scan(b) for b in body)
        # one level down only: a body's operation is no other loop's
    bodies = [b for body in table["inside"].values() for b in body]
    assert len(bodies) == len(set(bodies))


def test_a_hyper_connected_blocks_kernels_lie_under_hyper_conn():
    """A model whose four streams tile (hidden 128, 128 tokens, bfloat16)
    under ``remat="full"``: every operation of the fused passes — the six
    kernels' (interpreted here: their bodies carry the call's name), forward,
    made again and backward — has ``hyper_conn`` as its innermost layer scope,
    so that ``hyper_conn_ms`` reads them where it read the ``jnp`` forms."""
    import optax

    from benchmark.metrics import hyper_conn_ms, scope_time
    from ddw_tpu.models.lm import build_lm
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
    from ddw_tpu.utils.config import LayerSpec

    model = build_lm(LMCfg(
        vocab_size=64, max_len=64, hidden=128, depth=1, num_heads=2,
        mlp_dim=128, dtype="bfloat16", remat="full",
        layer=LayerSpec(hyper_streams=4, hyper_res_diag=1.5)))
    tx = optax.adam(1e-3)
    step = make_lm_train_step(model, tx, make_data_mesh(
        devices=jax.devices()[:1]), seq_axis=None)
    state = jax.eval_shape(lambda: init_lm_state(model, tx,
                                                 jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 64), np.int32)
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    table = parse_hlo(step.lower(state, tokens, tokens, key).compile()
                      .as_text())
    layers = (set(scope_time.scope_map()["layers"])
              | set(hyper_conn_ms.NEW_SCOPES))
    seen = set()
    for path in table["scopes"]:
        for kernel in ("hc_read_fwd", "hc_read_bwd", "hc_write_fwd",
                       "hc_write_bwd", "hc_sinkhorn_fwd", "hc_sinkhorn_bwd"):
            if kernel in path.split("/"):
                layer, which = scope_time.layer_of(path, layers)
                assert layer == "hyper_conn", path
                seen.add((kernel, which))
    assert seen >= {("hc_read_fwd", "fwd"), ("hc_read_fwd", "remat"),
                    ("hc_write_fwd", "fwd"), ("hc_write_fwd", "remat"),
                    ("hc_sinkhorn_fwd", "fwd"), ("hc_sinkhorn_fwd", "remat"),
                    ("hc_read_bwd", "bwd"), ("hc_write_bwd", "bwd"),
                    ("hc_sinkhorn_bwd", "bwd")}
    assert not any(which == "fwd" for kernel, which in seen
                   if kernel.endswith("_bwd"))


@pytest.mark.parametrize("op_name,path", [
    ("jit(_step)/shard_map/fwd_bwd/transpose(jvp(TransformerLM))/fwd_bwd/"
     "jvp(TransformerLM)/checkpoint/rematted_computation/backbone_block3/"
     "attn/attention/dot_general",
     "remat/fwd_bwd/TransformerLM/fwd_bwd/TransformerLM/backbone_block3/"
     "attn/attention"),
    ("jit(_step)/shard_map/fwd_bwd/transpose(jvp(TransformerLM))/head/"
     "dot_general", "bwd/fwd_bwd/TransformerLM/head"),
    ("jit(_step)/shard_map/fwd_bwd/jvp(TransformerLM)/embed/tok_embed/"
     "jit(_take)/gather", "fwd/fwd_bwd/TransformerLM/embed/tok_embed"),
    ("jit(_step)/shard_map/optimizer/mul;shard_map", "fwd/optimizer"),
    ("jit(_step)/shard_map", ""), ("ragged-dot", ""),
    ("jit(_step)/shard_map/broadcast.119", ""),
])
def test_a_path_is_the_name_stack_less_its_wrappers(op_name, path):
    assert scope_path(op_name) == path


# -- the loop -----------------------------------------------------------------
def _fit(tracer, **train_kw):
    lm = LMCfg(vocab_size=32, max_len=16, hidden=16, num_heads=2, mlp_dim=32,
               depth=1, dropout=0.0, dtype="float32")
    train = TrainCfg(batch_size=4, epochs=2, warmup_epochs=0, seed=0,
                     learning_rate=1e-2, num_devices=2, **train_kw)
    starts = np.random.RandomState(0).randint(0, 32, size=(36, 1))
    tokens = ((starts + np.arange(17)[None]) % 32).astype(np.int32)
    return LMTrainer(lm, train, tracer=tracer).fit(tokens, val_fraction=0.1)


@pytest.fixture()
def lowerings(monkeypatch):
    """Every ``step_table`` call and every ``.lower`` it makes."""
    calls = {"table": 0, "lower": 0}
    real = step_scopes.step_table

    class Spy:
        def __init__(self, step):
            self._step = step

        def lower(self, *args):
            calls["lower"] += 1
            return self._step.lower(*args)

    def counted(step, args):
        calls["table"] += 1
        return real(Spy(step), args)

    monkeypatch.setattr(loop, "step_table", counted)
    return calls


def test_without_a_tracer_nothing_is_lowered_or_built(lowerings):
    res = _fit(None)
    assert res.epochs_run == 2
    assert lowerings == {"table": 0, "lower": 0}


@pytest.mark.parametrize("k", [1, 2])
def test_a_traced_fit_records_the_table_of_its_one_executable(k, lowerings):
    tracer = Tracer(capacity=4096)
    _fit(tracer, steps_per_dispatch=k)
    assert lowerings == {"table": 1, "lower": 1}
    events = tracer.drain()
    (span,) = [e for e in events if e["name"] == "step_scopes"]
    table = span["args"]
    assert table["module"] == ("jit__chain" if k > 1 else "jit__step")
    assert DENSE <= _names(table)
    # made before the step's first dispatch, inside the first chain, after
    # its wait for the batch; the table's making is the span's length
    chains = [e for e in events if e["name"] == "train_chain"]
    first = min(chains, key=lambda e: e["ts"])
    assert span["parent"] == first["span"] and span["dur"] > 0
    waits, dispatches = ([e for e in events if e["name"] == n
                          and e["parent"] == first["span"]]
                         for n in ("data_wait", "dispatch"))
    assert waits[0]["ts"] + waits[0]["dur"] <= span["ts"] + 1
    assert span["ts"] + span["dur"] <= dispatches[0]["ts"] + 1
    # the table's own compile adds no executable to the step's
    epochs = [e for e in events if e["name"] == "epoch"]
    assert [e["args"]["step_variants"] for e in epochs] == [1, 1]


def test_the_span_survives_the_chrome_trace(tmp_path):
    tracer = Tracer(capacity=4096)
    _fit(tracer)
    path = tmp_path / "train_spans.trace.json"
    path.write_text(json.dumps(chrome_trace(tracer.drain())))
    (back,) = [e for e in load_events(str(path))
               if e["name"] == "step_scopes"]
    (span,) = [e for e in tracer.drain() if e["name"] == "step_scopes"]
    assert back["args"] == span["args"] and back["tid"] == "train"
    assert back["dur"] == span["dur"]
