"""Family ``lm_hybrid_ssm_moe_train``: a decoder LM with ONE mixer a layer —
Mamba-2 state-space layers, attention without positions, sigmoid-routed
``relu2`` experts beside a shared one on a chip's share — trained through
``LMTrainer.fit_tables`` like families ``lm_train`` and
``lm_sparse_moe_train``, whose harness, corpus and choice plumbing it reuses.

A configuration of this family holds the source's ``config.json`` keys
(``configs/nemotron-3-nano-30b-a3b.json`` is the pattern:
``hybrid_override_pattern``, the ``mamba_*`` and ``moe_*`` sizes,
``n_routed_experts`` = the experts held here, ``deployment`` with the
published counts, ``dt_bias_shift``); a traffic mix is ``lm_train``'s.
"""

from __future__ import annotations

import importlib
import math
import os
import types

from benchmark.families import lm_sparse_moe_train as sparse
from benchmark.families import lm_train
from benchmark.families.lm_train import (_train_cfg, make_corpus,  # noqa: F401
                                         reference_batch_shapes, tiny_batches)
from benchmark.harness import check, train_cell
from benchmark.harness.step_probe import FOLLOW

# the step factory LMTrainer._run calls; harness/step_probe.py wraps it
STEP_FACTORY = ("ddw_tpu.train.lm_trainer", "make_lm_train_step")

# sizes small enough for the CPU (rehearsal/tiny_nemotron3nano.py, the tests;
# benchmark/tests/test_flops_nemotron_h.py puts them into the benchmark's own
# table for its tests). The pattern stays the cell's.
TINY = {
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 16,
               "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
               "ssm_state_size": 16, "chunk_size": 8, "dt_bias_shift": -2.4,
               "moe_intermediate_size": 32,
               "moe_shared_expert_intermediate_size": 48,
               "n_routed_experts": 4, "num_experts_per_tok": 3,
               "vocab_size": 256, "max_position_embeddings": 64,
               "dtype": "float32",
               "deployment": {"published_n_routed_experts": 16,
                              "first_expert": 0}},
    "traffic": {"seq_len": 32, "batch_per_chip": 4, "steps_per_epoch": 4,
                "remat": "none", "reference_micro_rows": 2},
}


# -- required operations ------------------------------------------------------
def layer_params(config: dict) -> dict:
    """Parameters of ONE layer of each kind that take part in a matrix
    product once per token: ``M`` both projections; ``*`` the four; ``E`` the
    shared expert, the router at its published width and the routed experts a
    token is EXPECTED to meet here (``num_experts_per_tok`` times the share of
    the router's experts held). The convolution, ``A``, ``D``, ``dt``'s bias
    and the norms are left out."""
    d = config["hidden_size"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    bc = 2 * config["n_groups"] * config["ssm_state_size"]
    heads_wide = config["num_attention_heads"] * config["head_dim"]
    kv_wide = config["num_key_value_heads"] * config["head_dim"]
    width = config["deployment"]["published_n_routed_experts"]
    met = config["num_experts_per_tok"] * config["n_routed_experts"] / width
    return {"M": d * (2 * inner + bc + config["mamba_num_heads"]) + inner * d,
            "*": 2 * d * heads_wide + 2 * d * kv_wide,
            "E": (2 * d * config["moe_shared_expert_intermediate_size"]
                  + d * width + met * 2 * d * config["moe_intermediate_size"])}


def matmul_params(config: dict) -> float:
    per = layer_params(config)
    return (sum(per[kind] for kind in config["hybrid_override_pattern"])
            + config["hidden_size"] * config["vocab_size"])


def scan_flops_per_token(config: dict) -> float:
    """The chunked scan's own products, forward, a token and ``M`` layer:
    scores ``2 Q N G``, the masked product ``2 Q P H``, the state built and
    read ``4 N P H`` (``Q`` the chunk)."""
    q, n, g = config["chunk_size"], config["ssm_state_size"], config["n_groups"]
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    return 2.0 * q * n * g + 2.0 * q * p * h + 4.0 * n * p * h


def required_flops_per_item(config: dict, seq: int | None = None) -> float:
    """Forward and backward FLOPs one token requires at rows of ``seq`` tokens
    (``run`` below passes the traffic's; the configuration's longest without):
    6 per matmul parameter; an attention layer's two S-long products counted
    full as the other families do, ``12 * heads * head_dim * S``; the scan's
    own products times 3. Recomputation earns nothing."""
    s = seq or config["max_position_embeddings"]
    pattern = config["hybrid_override_pattern"]
    heads_wide = config["num_attention_heads"] * config["head_dim"]
    return (6.0 * matmul_params(config)
            + pattern.count("*") * 12.0 * heads_wide * s
            + pattern.count("M") * 3.0 * scan_flops_per_token(config))


def loss_at_random(config: dict) -> float:
    return math.log(config["vocab_size"])


# -- the reference, and how its leaves lie in the program's tree ---------------
def reference_spec(config: dict) -> dict:
    from benchmark.reference import nemotron_h

    return nemotron_h.weight_spec(config)


def reference_loss(config: dict, precision: str):
    from benchmark.reference import nemotron_h

    return nemotron_h.make_loss(config, precision)


hyper = lm_train.hyper

_TOP = {("tok_embed", "embedding"): "wte", ("RMSNorm_0", "scale"): "lnf.g",
        ("head", "kernel"): "head.w"}
_MIXER = {
    "M": {("in_proj",): "win", ("conv_kernel",): "conv.w",
          ("conv_bias",): "conv.b", ("A_log",): "alog", ("D",): "D",
          ("dt_bias",): "dtb", ("norm_scale",): "gn.g",
          ("out_proj",): "wout"},
    "*": {("query", "kernel"): "wq", ("key", "kernel"): "wk",
          ("value", "kernel"): "wv", ("out", "kernel"): "wo"},
    "E": {("gate", "kernel"): "router", ("w_up",): "w1", ("w_down",): "w2",
          ("shared_up", "kernel"): "s1", ("shared_down", "kernel"): "s2"}}


def leaf_map(config: dict) -> dict:
    """Program path -> reference key (``l<layer>.<leaf>``: the reference
    keeps a leaf a layer, no stacks)."""
    out = dict(_TOP)
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        out[(f"backbone_block{i}", "RMSNorm_0", "scale")] = f"l{i}.ln.g"
        for path, name in _MIXER[kind].items():
            out[(f"backbone_block{i}", "mixer") + path] = f"l{i}.{name}"
    return out


# -- the program's choices, handed to the reference -----------------------------
class ChoiceProbe:
    """Stands between the harness's ``StepProbe`` and the trainer's step for
    the steps the reference follows, and keeps the experts the step itself
    chose on them: the step is built with ``hand_out=("expert_choice",)``
    (``train/lm_step.py``) and returns its choices beside its metrics. A
    second forward pass (``lm_sparse_moe_train.ChoiceProbe`` reads Keye's
    that way) rounds otherwise in bfloat16 than the step's own, and a few
    choices in a thousand come out different. (The 0.8 of a gradient's length
    that one seed read that way was not this: the step's own backward pass
    chose again, PERF.md section 6, PR 34, second round.) Packed
    behind their rows' ids they wait in that family's table for
    ``reference_batch``. From the third step on calls only pass through, and
    the loop never sees the extra entry."""

    def __init__(self, inner, keep_gradient=None, start_bias=None):
        self._inner, self._keep_gradient = inner, keep_gradient
        self._start_bias = start_bias   # [E layers, width] or None
        self._calls = 0

    def __getattr__(self, name):        # batch_sharding, place_state, ...
        return getattr(self._inner, name)

    def __call__(self, state, inputs, *rest):
        import numpy as np

        from benchmark.reference.nemotron_h import attach_choices

        follow = self._calls < FOLLOW
        if self._calls == 1 and self._keep_gradient is not None:
            self._keep_gradient(state.opt_state)
        self._calls += 1
        rows = np.asarray(inputs) if follow else None
        if self._calls == 1 and self._start_bias is not None:
            state = state.replace(batch_stats=place_bias(
                state.batch_stats, self._start_bias))
        state, metrics = self._inner(state, inputs, *rest)
        chosen = metrics.pop("handed")["expert_choice"]
        if follow:
            sparse._CHOSEN[sparse._rows_key(rows)] = np.asarray(
                attach_choices(rows, chosen.reshape(
                    chosen.shape[0], *rows.shape, chosen.shape[-1])))
        return state, metrics


def place_bias(buffers, bias):
    """The program's buffers with routed layer ``j``'s ``router_bias`` set to
    ``bias[j]`` (``j`` counts the buffers' blocks by their number in the
    backbone, which is the pattern's order), each leaf where and as the
    program keeps it."""
    import jax

    blocks = sorted(buffers, key=lambda name: int(name.rpartition("block")[2]))
    if len(blocks) != len(bias):
        raise ValueError(f"the program keeps {len(blocks)} correction biases, "
                         f"the start has {len(bias)}")

    def put(path, leaf):
        own = bias[blocks.index(path[0].key)]
        return jax.device_put(own.reshape(leaf.shape).astype(leaf.dtype),
                              leaf.sharding)

    return jax.tree_util.tree_map_with_path(put, buffers)


def start_bias(config: dict, traffic: dict, seed: int, devices: list):
    """The correction biases the cell starts from, ``[E layers, width]`` on
    the host, or None where the configuration asks for none
    (``router_bias_start``): found by the reference at the seeded weights on
    the corpus's first training batch (``nemotron_h.balanced_bias``), handed
    to the program before step 1 (``ChoiceProbe``) and to the reference's
    ``choice_margins``. Runs, and frees what it made, before the trainer is
    built."""
    if config.get("router_bias_start", "zeros") != "balanced":
        return None
    import time

    import jax
    import numpy as np

    from benchmark.harness.weights import seed_key, seeded_weights
    from benchmark.reference import nemotron_h

    t0 = time.time()
    # the rows ``prepare`` writes first into the training table (it is handed
    # the seed as ``harness/train_cell.run`` cuts it)
    rows = traffic["batch_per_chip"] * len(devices)
    corpus = _corpus(config, traffic, int(seed) % (2 ** 31 - 1), rows)
    tokens = np.asarray(corpus[rows:2 * rows, :-1], np.int32)
    spec = reference_spec(config)
    bias = np.asarray(jax.jit(lambda key, x: nemotron_h.balanced_bias(
        seeded_weights(key, spec), x, config))(seed_key(seed), tokens))
    print(f"router_bias_start balanced: {bias.shape[0]} layers on "
          f"{tokens.size} tokens, range by layer "
          f"{[round(float(b.max() - b.min()), 4) for b in bias]}, took "
          f"{time.time() - t0:.1f} s", flush=True)
    return bias


def reference_batch(batch: tuple):
    """The reference's batch: the rows the program saw and, where
    ``ChoiceProbe`` followed the step (a run of the cell), the experts the
    step chose on them; rows nobody followed go plain, and the reference
    chooses for itself."""
    import numpy as np

    inputs = sparse._CHOSEN.get(sparse._rows_key(batch[0]), batch[0])
    return np.asarray(inputs, np.int32), np.asarray(batch[1], np.int32)


def choice_margins(config: dict, traffic: dict, seed: int, rows,
                   bias=None) -> dict:
    """The choices read on the first step's rows against the reference's own
    float32 scores at the seeded weights under the start's correction biases:
    ``nemotron_h.choice_margins``'s two numbers."""
    import jax

    from benchmark.harness.weights import seed_key, seeded_weights
    from benchmark.reference import nemotron_h

    spec = reference_spec(config)
    weights = jax.jit(lambda key: seeded_weights(key, spec))(seed_key(seed))
    margins = jax.jit(lambda w, x, b: nemotron_h.choice_margins(
        w, x, traffic["seq_len"], config, b))(weights, rows, bias)
    return {k: float(v) for k, v in margins.items()}


# -- the job ------------------------------------------------------------------
def _lm_cfg(config: dict, traffic: dict):
    from ddw_tpu.utils.config import LayerSpec, LMCfg

    dep = config["deployment"]
    layer = LayerSpec(
        norm="rmsnorm", norm_eps=config["norm_eps"],
        bias=config["attention_bias"], head_dim=config["head_dim"],
        mlp=config["mlp_hidden_act"],
        experts_per_token=config["num_experts_per_tok"],
        router_width=dep["published_n_routed_experts"],
        expert_offset=dep["first_expert"],
        norm_topk=config["norm_topk_prob"], router_score="sigmoid",
        router_scale=config["routed_scaling_factor"],
        router_bias_rate=config["router_bias_update_rate"],
        shared_expert_dim=config["moe_shared_expert_intermediate_size"],
        ssm_heads=config["mamba_num_heads"],
        ssm_head_dim=config["mamba_head_dim"], ssm_groups=config["n_groups"],
        ssm_state=config["ssm_state_size"], ssm_conv=config["conv_kernel"],
        ssm_chunk=config["chunk_size"],
        ssm_dt_shift=config.get("dt_bias_shift", 0.0),
        embed_scale=config.get("embedding_multiplier", 1.0))
    return LMCfg(vocab_size=config["vocab_size"],
                 max_len=config["max_position_embeddings"],
                 hidden=config["hidden_size"],
                 depth=config["num_hidden_layers"],
                 num_heads=config["num_attention_heads"],
                 num_kv_heads=config["num_key_value_heads"],
                 mlp_dim=config["moe_intermediate_size"],
                 num_experts=config["n_routed_experts"], dropout=0.0,
                 dtype=config["dtype"], pos_encoding="none",
                 remat=traffic["remat"], layer=layer,
                 pattern=config["hybrid_override_pattern"])


def _corpus(config: dict, traffic: dict, seed: int, global_batch: int):
    """One validation batch, then ``steps_per_epoch`` training batches."""
    return make_corpus(seed, (traffic["steps_per_epoch"] + 1) * global_batch,
                       traffic["seq_len"], config["vocab_size"])


def prepare(config: dict, traffic: dict, seed: int, work: str, devices: list):
    from ddw_tpu.data.prep import write_token_table
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_trainer import LMTrainer

    seq = traffic["seq_len"]
    global_batch = traffic["batch_per_chip"] * len(devices)
    spe = traffic["steps_per_epoch"]
    corpus = _corpus(config, traffic, seed, global_batch)
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


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices: list, peaks: dict | None, **kw) -> dict:
    """``harness/train_cell.run`` with the step's own choices of experts on
    the followed steps handed to the reference (``ChoiceProbe``,
    ``reference_batch``) and the reference's loop the lean one
    (``reference/optim_lean.py``: four float32 trees of this model are most
    of the chip), then what only this family knows: the required FLOPs at the
    traffic's row length; the layers' counters that have a right value (no
    assignment dropped; between a twentieth and nineteen twentieths of a
    state crossing a chunk, so that the carried state is neither invisible
    nor all there is); how far the choices handed over lie from the
    reference's own; and how far the program's first gradient lies from the
    reference's, leaf by leaf. All judged with the cell's limits."""
    from benchmark.reference import optim, optim_donating, optim_lean

    config = dict(cell.config, **(kw.get("tiny") or {}).get("config", {}))
    spec, mapping = reference_spec(config), leaf_map(config)

    module = importlib.import_module(STEP_FACTORY[0])
    real, kept_loop = getattr(module, STEP_FACTORY[1]), optim.run_steps
    sparse._CHOSEN.clear()
    optim_donating.hold_against(None)
    controls = kw.get("controls", ())
    traffic = dict(cell.traffic, **(kw.get("tiny") or {}).get("traffic", {}))
    bias = start_bias(config, traffic, seed, devices)
    setattr(module, STEP_FACTORY[1], lambda *a, **k: ChoiceProbe(
        real(*a, hand_out=("expert_choice",), **k),
        lambda opt_state: optim_donating.hold_against(
            sparse.first_gradient(opt_state, mapping, spec), bool(controls)),
        bias))
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
    sparse.print_counters(ctx["rows"], ("router_bias_range",
                                        "ssm_chunk_carry"))
    nan = float("nan")      # a step without the counters is not correct
    carried = [r.get("ssm_chunk_carry", nan) for r in ctx["rows"]]
    counted = {
        "moe_dropped": sparse._worst(r.get("moe_dropped", nan)
                                     for r in ctx["rows"]),
        "ssm_chunk_carry_least": -sparse._worst(-x for x in carried),
        "ssm_chunk_carry_most": sparse._worst(carried)}
    first = next(iter(sparse._CHOSEN.values()), None)   # the first step's rows
    counted.update(
        choice_margins(config, traffic, seed, first, bias)
        if first is not None
        else dict.fromkeys(("expert_choice_margin",
                            "experts_misplaced_share"), nan))
    sparse._CHOSEN.clear()
    gaps = [sparse._worst(s for _, s in g)
            for g in optim_donating.DIRECTION_GAPS]
    counted["grad_direction_gap"] = gaps[0] if gaps else nan
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
