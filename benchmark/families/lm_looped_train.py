"""Family ``lm_looped_train``: a decoder LM whose stack of sandwich-normed
blocks (a norm before AND after each sublayer) runs ``total_ut_steps`` times
over ONE set of weights, with an exit after every pass through the one head
and a learned gate that spreads a token over the exits — trained through
``LMTrainer.fit_tables`` like the other LM families, on the expected loss over
the exits with its entropy term.

A configuration of this family holds the source's ``config.json`` keys
(``configs/ouro-2.6b.json`` is the pattern: ``total_ut_steps``, ``head_dim``,
``layer_types`` all ``full_attention``, ``rope_scaling`` null, and
``exit_entropy_weight``, which the source does not have); a traffic mix is
``lm_train``'s plus ``moment_dtype``.
"""

from __future__ import annotations

import importlib
import math
import os
import types

from benchmark.families import lm_train
from benchmark.families.lm_sparse_moe_train import _worst, first_gradient
from benchmark.families.lm_train import (hyper, make_corpus,  # noqa: F401
                                         reference_batch,
                                         reference_batch_shapes, tiny_batches)
from benchmark.harness import check, train_cell
from benchmark.harness.step_probe import FOLLOW

# the step factory LMTrainer._run calls; harness/step_probe.py wraps it
STEP_FACTORY = ("ddw_tpu.train.lm_trainer", "make_lm_train_step")

# sizes small enough for the CPU (rehearsal/tiny_ouro.py, the tests), at the
# published RATIOS: heads x head = hidden, the MLP 2.75 times the hidden
# width, four passes
TINY = {
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 4, "head_dim": 16,
               "intermediate_size": 176, "num_hidden_layers": 2,
               "layer_types": ["full_attention"] * 2, "vocab_size": 256,
               "max_position_embeddings": 64, "dtype": "float32"},
    "traffic": {"seq_len": 32, "batch_per_chip": 2, "steps_per_epoch": 4,
                "remat": "full", "reference_micro_rows": 1,
                "moment_dtype": "float32"},
}


# -- required operations ------------------------------------------------------
def layer_params(config: dict) -> int:
    """One layer's parameters that take part in a matrix product: the four
    attention projections and the three of the SwiGLU MLP. The four norms'
    gains are left out."""
    d = config["hidden_size"]
    e = config["num_attention_heads"] * config["head_dim"]
    return 4 * d * e + 3 * d * config["intermediate_size"]


def held_params(config: dict) -> int:
    """Every parameter this chip holds: the layers with their four gains
    each, the embedding and the untied head, the final norm, the gate with
    its bias."""
    d = config["hidden_size"]
    return (config["num_hidden_layers"] * (layer_params(config) + 4 * d)
            + 2 * config["vocab_size"] * d + d + d + 1)


def matmul_params(config: dict) -> int:
    """Parameters times the uses a token makes of each: every layer, the head
    and the gate's projection once a pass."""
    d, passes = config["hidden_size"], config["total_ut_steps"]
    return passes * (config["num_hidden_layers"] * layer_params(config)
                     + d * config["vocab_size"] + d)


def applications(config: dict) -> int:
    """Block applications a token: the layers times the passes."""
    return config["num_hidden_layers"] * config["total_ut_steps"]


def required_flops_per_item(config: dict, seq: int | None = None) -> float:
    """Forward and backward FLOPs one token requires at rows of ``seq`` tokens
    (``run`` below passes the traffic's; the declared context without), EVERY
    USE of a weight counted: 6 per matmul parameter and use, the head and the
    gate once a pass; every block application's two S-long products counted
    full as the other families do, ``12 S heads head_dim``. Recomputation
    (``remat``, the exits' logits made again) earns nothing."""
    s = seq or config["max_position_embeddings"]
    wide = config["num_attention_heads"] * config["head_dim"]
    return (6.0 * matmul_params(config)
            + 12.0 * applications(config) * wide * s)


def attention_kernel_flops_per_step(config: dict, traffic: dict,
                                    chips: int = 1) -> float:
    """What the attention kernels of one step have to compute, forward and
    backward, for ``mla_attention_roofline_pct`` (whose reader is generic):
    every block application's causal pairs, ``B heads S (S + 1) / 2``, times
    2 FLOPs times the seven products of forward and backward at ``head_dim``
    (four with q or k, three with v: the formula that reader documents, at
    equal widths). A second making of the scores, padded lanes and masked
    pairs inside a block earn nothing, so the share cannot pass 100 %."""
    s, rows = traffic["seq_len"], traffic["batch_per_chip"] * chips
    pairs = rows * config["num_attention_heads"] * s * (s + 1) / 2
    return applications(config) * pairs * 2.0 * 7 * config["head_dim"]


def loss_at_random(config: dict) -> float:
    """The reported loss is the LAST exit's cross-entropy."""
    return math.log(config["vocab_size"])


# -- the reference, and how its leaves lie in the program's tree ---------------
def reference_spec(config: dict) -> dict:
    from benchmark.reference import ouro

    return ouro.weight_spec(config)


def reference_loss(config: dict, precision: str):
    from benchmark.reference import ouro

    return ouro.make_loss(config, precision)


_TOP = {("tok_embed", "embedding"): "wte", ("RMSNorm_0", "scale"): "lnf.g",
        ("head", "kernel"): "head.w", ("exit_gate", "kernel"): "gate.w",
        ("exit_gate", "bias"): "gate.b"}
_BLOCK = {("RMSNorm_0", "scale"): "ln1.g",
          ("attn_post_norm", "scale"): "ln2.g",
          ("RMSNorm_1", "scale"): "ln3.g",
          ("mlp_post_norm", "scale"): "ln4.g",
          ("attn", "query", "kernel"): "wq", ("attn", "key", "kernel"): "wk",
          ("attn", "value", "kernel"): "wv", ("attn", "out", "kernel"): "wo",
          ("gate", "kernel"): "wg", ("up", "kernel"): "wu",
          ("down", "kernel"): "wd"}


def leaf_map(config: dict) -> dict:
    """Program path -> reference key (``l<layer>.<leaf>``: the reference keeps
    a leaf a layer, no stacks). The program's ``[hidden, heads, head]``
    projection kernels are the reference's ``[hidden, heads x head]``
    reshaped."""
    out = dict(_TOP)
    for i in range(config["num_hidden_layers"]):
        for path, name in _BLOCK.items():
            out[(f"backbone_block{i}",) + path] = f"l{i}.{name}"
    return out


# -- the job ------------------------------------------------------------------
def _lm_cfg(config: dict, traffic: dict):
    from ddw_tpu.utils.config import LayerSpec, LMCfg

    if config["rope_scaling"] is not None or config["use_sliding_window"]:
        raise ValueError("this family turns by plain RoPE and attends to "
                         "every causal key: rope_scaling null, no window")
    kinds = config["layer_types"]
    if (len(kinds) != config["num_hidden_layers"]
            or set(kinds) != {"full_attention"}):
        raise ValueError("layer_types has to name every layer held, each "
                         "full_attention")
    if config["hidden_act"] != "silu" or config["tie_word_embeddings"]:
        raise ValueError("this family's MLP is SwiGLU and its head untied")
    layer = LayerSpec(norm="rmsnorm", norm_eps=config["rms_norm_eps"],
                      bias=False, head_dim=config["head_dim"],
                      rope_theta=config["rope_theta"], mlp="swiglu",
                      post_norm=True)
    return LMCfg(vocab_size=config["vocab_size"],
                 max_len=config["max_position_embeddings"],
                 hidden=config["hidden_size"],
                 depth=config["num_hidden_layers"],
                 num_heads=config["num_attention_heads"],
                 num_kv_heads=config["num_key_value_heads"],
                 mlp_dim=config["intermediate_size"], dropout=0.0,
                 dtype=config["dtype"], pos_encoding="rope",
                 remat=traffic["remat"], layer=layer,
                 passes=config["total_ut_steps"], exit_gate=True)


def _train_cfg(config: dict, traffic: dict, seed: int):
    import dataclasses

    return dataclasses.replace(
        lm_train._train_cfg(traffic, seed),
        moment_dtype=traffic.get("moment_dtype", "float32"),
        exit_entropy_weight=config["exit_entropy_weight"])


def prepare(config: dict, traffic: dict, seed: int, work: str, devices: list):
    from ddw_tpu.data.prep import write_token_table
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_trainer import LMTrainer

    seq = traffic["seq_len"]
    global_batch = traffic["batch_per_chip"] * len(devices)
    # one validation batch, then steps_per_epoch training batches
    corpus = make_corpus(seed, (traffic["steps_per_epoch"] + 1) * global_batch,
                         seq, config["vocab_size"])
    store = TableStore(os.path.join(work, "lm_tables"))
    train_tbl = write_token_table(store, "train", corpus[global_batch:])
    val_tbl = write_token_table(store, "val", corpus[:global_batch])

    lm_cfg = _lm_cfg(config, traffic)
    train_cfg = _train_cfg(config, traffic, seed)
    mesh = make_data_mesh(devices=devices)

    def fit(run, tracer):
        trainer = LMTrainer(lm_cfg, train_cfg, mesh=mesh, run=run,
                            tracer=tracer)
        return trainer.fit_tables(train_tbl, val_tbl)

    return types.SimpleNamespace(fit=fit,
                                 steps_per_epoch=traffic["steps_per_epoch"],
                                 items_per_step=global_batch * seq)


class ExitProbe:
    """Stands between the harness's ``StepProbe`` and the trainer's step for
    the steps the reference follows: keeps the first step's rows and each
    followed step's counters of the exits (device scalars until read), and
    hands ``keep_gradient`` the optimizer state once step 1 has left the
    first gradient in Adam's first moment. From the third step on calls only
    pass through."""

    def __init__(self, inner, keep_gradient, followed: list):
        self._inner, self._keep_gradient = inner, keep_gradient
        self._followed, self._calls = followed, 0

    def __getattr__(self, name):        # batch_sharding, place_state, ...
        return getattr(self._inner, name)

    def __call__(self, state, inputs, targets, *rest):
        n = self._calls
        self._calls += 1
        if n == 1:
            self._keep_gradient(state.opt_state)
        rows = None
        if n == 0:
            import numpy as np

            rows = (np.asarray(inputs), np.asarray(targets))
        state, metrics = self._inner(state, inputs, targets, *rest)
        if n < FOLLOW:
            self._followed.append((rows, metrics.get("layers", {})))
        return state, metrics


def exit_gaps(config: dict, seed: int, followed: list) -> dict:
    """The first step's counters of the exits against the reference's own at
    the seeded weights on the same rows (``reference/ouro.py::terms``, one
    float32 forward pass): the gap of the TOTAL loss (what is descended: the
    program's ``exit_expected_loss`` less ``exit_entropy_weight`` times its
    ``exit_entropy``), the largest gap of an exit's mean cross-entropy, and
    the largest absolute gap of an exit's mean share ``p_t``. NaN where the
    step reported no such counter."""
    import jax

    from benchmark.harness.weights import seed_key, seeded_weights
    from benchmark.reference import ouro

    nan = float("nan")
    names = ("total_loss_gap", "exit_loss_gap", "exit_share_gap")
    if not followed or followed[0][0] is None:
        return dict.fromkeys(names, nan)
    (inputs, targets), counters = followed[0]
    spec = reference_spec(config)
    ref = {k: v.reshape(-1).tolist() for k, v in jax.device_get(jax.jit(
        lambda key, x, y: ouro.terms(seeded_weights(key, spec), x, y,
                                     config))(
            seed_key(seed), inputs, targets)).items()}
    got = {k: float(v) for k, v in jax.device_get(counters).items()}
    passes = range(1, config["total_ut_steps"] + 1)
    total = (got.get("exit_expected_loss", nan)
             - config["exit_entropy_weight"] * got.get("exit_entropy", nan))
    print(f"exits at step 1: program {got}; reference {ref}", flush=True)
    return {
        "total_loss_gap": abs(total - ref["total"][0]) / abs(ref["total"][0]),
        "exit_loss_gap": _worst(
            abs(got.get(f"exit_loss_{t}", nan) - ref["exit_loss"][t - 1])
            / ref["exit_loss"][t - 1] for t in passes),
        "exit_share_gap": _worst(
            abs(got.get(f"exit_share_{t}", nan) - ref["exit_share"][t - 1])
            for t in passes)}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices: list, peaks: dict | None, **kw) -> dict:
    """``harness/train_cell.run`` with the reference's loop the lean one (a
    float32 tree of this model is 2.04 GB), then what only this family knows:
    the required FLOPs at the traffic's row length; the exits' counters of
    the first step against the reference's (``exit_gaps``); and how far the
    program's first gradient lies from the reference's, leaf by leaf
    (``reference/optim_donating.py``). All judged with the cell's limits."""
    from benchmark.reference import optim, optim_donating, optim_lean

    tiny = kw.get("tiny") or {}
    config = dict(cell.config, **tiny.get("config", {}))
    traffic = dict(cell.traffic, **tiny.get("traffic", {}))
    # a program that cannot say this model (a parent commit) fails here, at
    # once
    _lm_cfg(config, traffic)
    spec, mapping = reference_spec(config), leaf_map(config)

    module = importlib.import_module(STEP_FACTORY[0])
    real, kept_loop = getattr(module, STEP_FACTORY[1]), optim.run_steps
    optim_donating.hold_against(None)
    controls = kw.get("controls", ())
    followed: list = []
    setattr(module, STEP_FACTORY[1], lambda *a, **k: ExitProbe(
        real(*a, **k),
        lambda opt_state: optim_donating.hold_against(
            first_gradient(opt_state, mapping, spec), bool(controls)),
        followed))
    optim.run_steps = optim_lean.run_steps
    try:
        result = train_cell.run(cell, seed, seconds, trace, t_start, devices,
                                peaks, **kw)
    finally:
        setattr(module, STEP_FACTORY[1], real)
        optim.run_steps = kept_loop
    ctx = result["ctx"]
    config, traffic = ctx["config"], ctx["traffic"]
    ctx["flops_per_item"] = required_flops_per_item(config, traffic["seq_len"])
    rows = ctx["rows"]
    names = (["exit_expected_passes", "exit_entropy"]
             + [f"exit_{what}_{t + 1}" for what in ("loss", "share")
                for t in range(config["total_ut_steps"])])
    print("counters by epoch " + str([
        {k: round(r[k], 4) for k in names if k in r} for r in rows]),
        flush=True)
    # a step without the counters reads NaN here, and is not correct
    counted = exit_gaps(config, seed, followed)
    gaps = [_worst(s for _, s in g) for g in optim_donating.DIRECTION_GAPS]
    counted["grad_direction_gap"] = gaps[0] if gaps else float("nan")
    for precision, gap in zip(controls, gaps[1:]):
        result["controls"][precision]["grad_direction_gap"] = gap
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
    train_cfg = _train_cfg(config, traffic, 0)
    tx = make_optimizer(train_cfg)
    step = make_lm_train_step(
        model, tx, mesh, seq_axis=None,
        exit_entropy_weight=train_cfg.exit_entropy_weight)
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
