"""Family ``lm_sparse_moe_train``: a decoder LM whose layers choose their keys
with an indexer and route to a chip's share of gated experts, trained through
``LMTrainer.fit_tables`` like family ``lm_train``.

A configuration of this family holds the source's ``config.json`` keys
(``configs/keye-vl-2.0-30b-a3b.json`` is the pattern: ``sa_config``,
``rope_scaling``, ``num_experts`` = the experts held here, ``deployment`` with
the published counts); a traffic mix is ``lm_train``'s.
"""

from __future__ import annotations

import hashlib
import importlib
import math
import os
import types

from benchmark.families import lm_train
from benchmark.families.lm_train import (_train_cfg, make_corpus,  # noqa: F401
                                         reference_batch_shapes, tiny_batches)
from benchmark.harness import check, train_cell
from benchmark.harness.step_probe import FOLLOW

# the step factory LMTrainer._run calls; harness/step_probe.py wraps it
STEP_FACTORY = ("ddw_tpu.train.lm_trainer", "make_lm_train_step")

# sizes small enough for the CPU (rehearsal/tiny_keyevl2.py, the tests;
# benchmark/tests/conftest.py puts them into the benchmark's own table for
# its tests, which look every cell's family up there)
TINY = {
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16,
               "moe_intermediate_size": 32, "num_experts": 4,
               "num_local_experts": 4, "num_experts_per_tok": 4,
               "num_hidden_layers": 2, "vocab_size": 256,
               "max_position_embeddings": 64, "dtype": "float32",
               "embedding_multiplier": 8.0,
               "rope_scaling": {"mrope_section": [2, 3, 3]},
               "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                             "indexer_num_kv_heads": 1, "kv_chunk_size": 16,
                             "q_chunk_size": 16, "topk": 8},
               "deployment": {"published_num_experts": 16,
                              "first_expert": 0}},
    "traffic": {"seq_len": 32, "batch_per_chip": 4, "steps_per_epoch": 4,
                "remat": "none", "reference_micro_rows": 2},
}


# -- required operations ------------------------------------------------------
def keys_per_query(seq: int, topk: int) -> float:
    """Mean over the queries of a row of ``min(t + 1, topk)``."""
    full = min(seq, topk)
    return (full * (full + 1) / 2 + (seq - full) * topk) / seq


def matmul_params(config: dict) -> float:
    """Parameters that take part in a matrix product once per token: a layer's
    four attention projections, the indexer's three, the router, the experts a
    token is EXPECTED to meet here (``num_experts_per_tok`` times the share of
    the router's experts held), and the output head. Embedding look-ups and
    norms are left out."""
    d, hd = config["hidden_size"], config["head_dim"]
    sa, dep = config["sa_config"], config["deployment"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    attention = 2 * d * heads * hd + 2 * d * kv * hd
    indexer = d * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        + d * sa["indexer_head_dim"] + d * sa["indexer_num_heads"]
    router = d * dep["published_num_experts"]
    met = (config["num_experts_per_tok"] * config["num_experts"]
           / dep["published_num_experts"])
    expert = 3 * d * config["moe_intermediate_size"]
    return (config["num_hidden_layers"]
            * (attention + indexer + router + met * expert)
            + d * config["vocab_size"])


def required_flops_per_item(config: dict, seq: int | None = None) -> float:
    """Forward and backward FLOPs one token requires at rows of ``seq`` tokens
    (``run`` below passes the traffic's; the configuration's longest without):
    6 per matmul parameter; the main attention's two products over the CHOSEN
    keys only, ``12 * heads * head_dim * keys_per_query`` a layer; the index
    scores forward over the causal keys (mean ``(S + 1) / 2``) and backward
    over the chosen ones, ``2 * J * Di`` a pair forward and twice that
    backward. Work on keys that were not chosen, and anything recomputed,
    earns nothing."""
    s = seq or config["max_position_embeddings"]
    sa = config["sa_config"]
    chosen = keys_per_query(s, sa["topk"])
    heads_wide = config["num_attention_heads"] * config["head_dim"]
    index_wide = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    per_layer = (12.0 * heads_wide * chosen
                 + 2.0 * index_wide * (s + 1) / 2 + 4.0 * index_wide * chosen)
    return (6.0 * matmul_params(config)
            + config["num_hidden_layers"] * per_layer)


def loss_at_random(config: dict) -> float:
    return math.log(config["vocab_size"])


# -- the reference, and how its leaves lie in the program's tree ---------------
def reference_spec(config: dict) -> dict:
    from benchmark.reference import keye_vl2

    return keye_vl2.weight_spec(config)


def reference_loss(config: dict, precision: str):
    from benchmark.reference import keye_vl2

    return keye_vl2.make_loss(config, precision)


hyper = lm_train.hyper


# -- the program's choices, handed to the reference -----------------------------
# digest of a followed step's token rows -> what ChoiceProbe kept of that step
# (model, parameters or how to make them, rows) and, once read, the rows with
# the program's choices packed behind the ids
_FOLLOWED: dict = {}
_CHOSEN: dict = {}


def _rows_key(inputs) -> str:
    import numpy as np

    return hashlib.sha1(np.ascontiguousarray(inputs, np.int32).tobytes()
                        ).hexdigest()


class ChoiceProbe:
    """Stands between the harness's ``StepProbe`` and the trainer's step for
    the steps the reference follows, and keeps what it takes to read the
    program's choices on them LATER: the rows, and the step's parameters —
    the first step's are the seeded ones and are made again from the seed,
    the second's are copied to the host (1.9 GB; with the first gradient,
    which ``keep_gradient`` is handed as the optimizer state after step 1
    holds it, the one cost this leaves in the timed set-up). Reading the
    choices is one more forward pass of the program's own code, compiled and
    run for the reference's sake alone, so it waits until the window is over
    (``reference_batch``). From the third step on calls only pass through."""

    def __init__(self, inner, model, seeded, keep_gradient=None):
        self._inner, self._model, self._seeded = inner, model, seeded
        self._keep_gradient = keep_gradient
        self._calls = 0

    def __getattr__(self, name):        # batch_sharding, place_state, ...
        return getattr(self._inner, name)

    def __call__(self, state, inputs, *rest):
        if self._calls < FOLLOW:
            import jax
            import numpy as np

            rows = np.asarray(inputs)
            if self._calls == 0:    # StepProbe has just put the seeded in
                shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    state.params)
                params = lambda: self._seeded(shapes)       # noqa: E731
            else:
                host = jax.device_get(state.params)
                params = lambda: host                       # noqa: E731
                if self._keep_gradient is not None:
                    self._keep_gradient(state.opt_state)
            _FOLLOWED[_rows_key(rows)] = (self._model, params, rows)
        self._calls += 1
        return self._inner(state, inputs, *rest)


def first_gradient(opt_state, mapping: dict, spec: dict) -> dict:
    """The program's first gradient on the host, named and shaped as the
    reference's weights: Adam's first moment after step 1 is ``(1 - b1) * g``
    (what ``StepProbe`` reads the gradient's norms from)."""
    import jax
    import numpy as np

    from benchmark.harness.step_probe import (ADAM_B1, path_names,
                                              split_ref_key)

    mus = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
        names = path_names(path)
        if "mu" in names:
            mus[mapping[names[names.index("mu") + 1:]]] = leaf
    out: dict = {}
    for key, leaf in jax.device_get(mus).items():
        name, layer = split_ref_key(key)
        shape = spec[name][0]
        value = np.asarray(leaf, np.float32) / (1 - ADAM_B1)
        if layer is None:
            out[name] = value.reshape(shape)
        else:
            out.setdefault(name, np.empty(shape, np.float32))[layer] = (
                value.reshape(shape[1:]))
    return out


def read_choices(model, params, inputs):
    """``inputs`` with the choices the model makes on them at ``params``
    packed behind each row's ids (the layers sow both choices; a train step
    never builds them)."""
    import jax.numpy as jnp

    from benchmark.reference.keye_vl2 import attach_choices

    _, mods = model.apply({"params": params}, inputs, train=True,
                          mutable=["intermediates"])
    layers = [mods["intermediates"][f"backbone_block{i}"]
              for i in range(model.depth)]
    keys = jnp.stack([l["attn"]["key_choice"][0] for l in layers])
    experts = jnp.stack([l["moe"]["expert_choice"][0] for l in layers])
    return attach_choices(
        inputs, keys, experts.reshape(len(layers), *inputs.shape, -1))


def reference_batch(batch: tuple):
    """The reference's batch: the rows the program saw and, where
    ``ChoiceProbe`` followed the step (a run of the cell), the choices the
    program made on them, read here — after the window, before the reference
    runs. The reference then follows the choices and the comparison is of the
    rest (PERF.md section 2 says why); rows nobody followed go plain, and the
    reference chooses for itself."""
    import jax
    import numpy as np

    key = _rows_key(batch[0])
    if key in _FOLLOWED:
        model, params, rows = _FOLLOWED.pop(key)
        _CHOSEN[key] = np.asarray(jax.jit(read_choices, static_argnums=0)(
            model, params(), rows))
    # host arrays: the loop moves a block of rows at a time to the device,
    # and rows with their choices are 35 MB each
    inputs = _CHOSEN.get(key, batch[0])
    return np.asarray(inputs, np.int32), np.asarray(batch[1], np.int32)


def choice_margins(config: dict, traffic: dict, seed: int, rows) -> dict:
    """The choices read on the first step's rows against the reference's own
    float32 scores at the seeded weights (the second step's weights are the
    reference loop's own and are not kept): ``keye_vl2.choice_margins``'s four
    numbers."""
    import jax

    from benchmark.harness.weights import seed_key, seeded_weights
    from benchmark.reference import keye_vl2

    spec = reference_spec(config)
    weights = jax.jit(lambda key: seeded_weights(key, spec))(seed_key(seed))
    margins = jax.jit(lambda w, x: keye_vl2.choice_margins(
        w, x, traffic["seq_len"], config))(weights, rows)
    return {k: float(v) for k, v in margins.items()}


_TOP = {("tok_embed", "embedding"): "wte", ("RMSNorm_0", "scale"): "lnf.g",
        ("head", "kernel"): "head.w"}
_BLOCK = {("RMSNorm_0", "scale"): "ln1.g", ("RMSNorm_1", "scale"): "ln2.g",
          ("attn", "query", "kernel"): "attn.wq",
          ("attn", "key", "kernel"): "attn.wk",
          ("attn", "value", "kernel"): "attn.wv",
          ("attn", "out", "kernel"): "attn.wo",
          ("attn", "q_norm", "scale"): "attn.qn.g",
          ("attn", "k_norm", "scale"): "attn.kn.g",
          ("attn", "index_q", "kernel"): "idx.wq",
          ("attn", "index_k", "kernel"): "idx.wk",
          ("attn", "index_w", "kernel"): "idx.ww",
          ("attn", "index_k_norm", "scale"): "idx.kn.g",
          ("attn", "index_k_norm", "bias"): "idx.kn.b",
          ("moe", "gate", "kernel"): "moe.router",
          ("moe", "w_gate"): "moe.gate", ("moe", "w_up"): "moe.up",
          ("moe", "w_down"): "moe.down"}


def leaf_map(config: dict) -> dict:
    """Program path -> reference key. The program's ``[hidden, heads, head]``
    projection kernels are the reference's ``[hidden, heads * head]`` reshaped
    (heads are contiguous column blocks in both)."""
    out = dict(_TOP)
    for i in range(config["num_hidden_layers"]):
        for path, name in _BLOCK.items():
            out[(f"backbone_block{i}",) + path] = f"blk.{name}@{i}"
    return out


# -- the job ------------------------------------------------------------------
def _lm_cfg(config: dict, traffic: dict):
    from ddw_tpu.utils.config import LayerSpec, LMCfg

    sa, dep = config["sa_config"], config["deployment"]
    layer = LayerSpec(
        norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        bias=config["attention_bias"], head_dim=config["head_dim"],
        qk_norm=True, rope_theta=float(config["rope_theta"]),
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        attention="indexed", index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        index_tile=sa["q_chunk_size"], mlp="swiglu",
        experts_per_token=config["num_experts_per_tok"],
        router_width=dep["published_num_experts"],
        expert_offset=dep["first_expert"],
        norm_topk=config["norm_topk_prob"],
        embed_scale=config.get("embedding_multiplier", 1.0))
    return LMCfg(vocab_size=config["vocab_size"],
                 max_len=config["max_position_embeddings"],
                 hidden=config["hidden_size"],
                 depth=config["num_hidden_layers"],
                 num_heads=config["num_attention_heads"],
                 num_kv_heads=config["num_key_value_heads"],
                 mlp_dim=config["moe_intermediate_size"],
                 num_experts=config["num_experts"], dropout=0.0,
                 dtype=config["dtype"], pos_encoding="rope",
                 remat=traffic["remat"], layer=layer)


def prepare(config: dict, traffic: dict, seed: int, work: str, devices: list):
    from ddw_tpu.data.prep import write_token_table
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_trainer import LMTrainer

    seq = traffic["seq_len"]
    global_batch = traffic["batch_per_chip"] * len(devices)
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


def direction_gaps(leaves: list) -> dict:
    """``optim_donating``'s ``(leaf, share)`` pairs as the two judged numbers."""
    return {"grad_direction_gap": _worst(s for _, s in leaves),
            "indexer_direction_gap": _worst(
                s for k, s in leaves if k.startswith("blk.idx."))}


def _worst(values) -> float:
    """The largest; NaN if any is."""
    values = list(values)
    return (max(values) if values and all(v == v for v in values)
            else float("nan"))


def print_counters(rows: list, more: tuple = ()):
    """A step's time follows the assignments to the held experts: say how
    they, and the family's ``more`` counters, moved from epoch to epoch, in
    every run (PERF.md section 2; ``tools/epoch_table.py`` reads the line)."""
    names = ("moe_assignments_per_token", "moe_load_max_over_mean",
             "moe_rows_run_share") + more
    print("counters by epoch " + str([
        {k: round(r[k], 4) for k in names if k in r} for r in rows]),
        flush=True)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices: list, peaks: dict | None, **kw) -> dict:
    """``harness/train_cell.run`` with the program's choices on the followed
    steps handed to the reference (``ChoiceProbe``, ``reference_batch``), then
    what only this family knows: the required FLOPs at the traffic's row
    length; the layers' two counters that have one right value (no assignment
    dropped; the chosen keys a query that the row length and ``topk`` give);
    how far the choices handed over lie from the reference's own; and how far
    the program's first gradient lies from the reference's, leaf by leaf
    (``reference/optim_donating.py``: the norms the harness compares hardly
    move with precision at this size). All judged with the cell's limits."""
    import jax

    from benchmark.harness.step_probe import program_tree
    from benchmark.harness.weights import seed_key, seeded_weights
    from benchmark.reference import optim, optim_donating

    config = dict(cell.config, **(kw.get("tiny") or {}).get("config", {}))
    spec, mapping = reference_spec(config), leaf_map(config)

    def seeded(shapes):     # the seeded weights as the program's tree
        return jax.jit(lambda key: program_tree(
            shapes, mapping, seeded_weights(key, spec)))(seed_key(seed))

    module = importlib.import_module(STEP_FACTORY[0])
    real, kept_loop = getattr(module, STEP_FACTORY[1]), optim.run_steps
    _FOLLOWED.clear(), _CHOSEN.clear()
    optim_donating.hold_against(None)
    controls = kw.get("controls", ())
    setattr(module, STEP_FACTORY[1], lambda model, *a, **k: ChoiceProbe(
        real(model, *a, **k), model, seeded,
        lambda opt_state: optim_donating.hold_against(
            first_gradient(opt_state, mapping, spec), bool(controls))))
    # the reference's loop, with a step's buffers handed on: nine float32
    # trees of this model do not fit the chip (reference/optim_donating.py)
    optim.run_steps = optim_donating.run_steps
    try:
        result = train_cell.run(cell, seed, seconds, trace, t_start, devices,
                                peaks, **kw)
    finally:
        setattr(module, STEP_FACTORY[1], real)
        optim.run_steps = kept_loop
        _FOLLOWED.clear()
    ctx = result["ctx"]
    config, traffic = ctx["config"], ctx["traffic"]
    ctx["flops_per_item"] = required_flops_per_item(config, traffic["seq_len"])
    print_counters(ctx["rows"])
    want = keys_per_query(traffic["seq_len"], config["sa_config"]["topk"])
    nan = float("nan")      # a step without the counters is not correct
    counted = {
        "moe_dropped": _worst(r.get("moe_dropped", nan) for r in ctx["rows"]),
        "keys_per_query_gap": _worst(abs(r.get("keys_per_query", nan) - want)
                                     for r in ctx["rows"])}
    first = next(iter(_CHOSEN.values()), None)      # the first step's rows
    counted.update(
        choice_margins(config, traffic, seed, first) if first is not None
        else dict.fromkeys(("key_choice_margin", "expert_choice_margin",
                            "keys_misplaced_share",
                            "experts_misplaced_share"), nan))
    _CHOSEN.clear()
    # the first gradient's distance from the reference's, leaf by leaf
    # (reference/optim_donating.py), the program's and then each control's:
    # by the worst leaf of all, and by the worst of the indexer's, which get
    # their gradient from the KL term alone and no choice of an expert or a
    # key flips it (PERF.md section 2)
    gaps = [direction_gaps(g) for g in optim_donating.DIRECTION_GAPS]
    counted.update(gaps[0] if gaps else direction_gaps([]))
    for precision, gap in zip(controls, gaps[1:]):
        result["controls"][precision].update(gap)
    optim_donating.hold_against(None)
    result["correct"] = bool(check.judge(counted, cell.limits)
                             and result["correct"])
    result["numbers"].update(counted)
    return result


def _step_and_state_shapes(config: dict, traffic: dict, devices: list):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ddw_tpu.models.lm import build_lm
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
    from ddw_tpu.train.step import make_optimizer

    mesh = make_data_mesh(devices=devices)
    model = build_lm(_lm_cfg(config, traffic))
    tx = make_optimizer(_train_cfg(traffic, 0))
    step = make_lm_train_step(model, tx, mesh, seq_axis=None)
    make_state = lambda: init_lm_state(model, tx, jax.random.PRNGKey(0))  # noqa: E731
    return step, make_state, NamedSharding(mesh, P()), NamedSharding(
        mesh, P("data"))


def compile_step(config: dict, traffic: dict, devices: list):
    """Compile-only rehearsal: the trainer's step at the cell's full size for
    described devices (``rehearsal/compile_cells.py``). Nothing runs."""
    import jax
    import jax.numpy as jnp

    step, make_state, repl, rows = _step_and_state_shapes(config, traffic,
                                                          devices)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(make_state))
    toks = jax.ShapeDtypeStruct(
        (traffic["batch_per_chip"] * len(devices), traffic["seq_len"]),
        jnp.int32, sharding=rows)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    return step.lower(state, toks, toks, key).compile()


def bare_step(config: dict, traffic: dict, devices: list):
    """The trainer's compiled step with a state and one batch, outside ``fit``
    (``tools/barrier_check.py`` only)."""
    import jax

    step, make_state, _, _ = _step_and_state_shapes(config, traffic, devices)
    rows = traffic["batch_per_chip"] * len(devices)
    corpus = make_corpus(0, rows, traffic["seq_len"], config["vocab_size"])
    batch = tuple(jax.device_put(x, step.batch_sharding)
                  for x in (corpus[:, :-1], corpus[:, 1:]))
    return step, make_state(), batch
