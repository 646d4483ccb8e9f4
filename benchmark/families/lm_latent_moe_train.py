"""Family ``lm_latent_moe_train``: a decoder LM with latent attention (MLA:
low-rank q and kv paths, one shared rotary key head, q/k heads wider than v
heads, plain RoPE) on ONE pre-norm residual stream, a leading dense layer
before sigmoid-routed SwiGLU experts beside a shared one on a chip's share,
and a multi-token-prediction module — trained through
``LMTrainer.fit_tables`` like the other LM families. It is
``lm_latent_hc_moe_train`` without the hyper-connected streams and without
YaRN, and imports from it everything that does not read those keys (the
choice plumbing, the placing of the start's correction biases, the train
configuration, the corpus, the attention kernels' operations).

A configuration of this family holds the source's ``config.json`` keys
(``configs/joyai-llm-flash.json`` is the pattern: the ``*_lora_rank`` and
``*_head_dim`` widths, ``rope_scaling`` null, ``first_k_dense_replace``,
``num_nextn_predict_layers``, ``n_routed_experts`` = the experts held here,
``deployment`` with the published counts, ``mtp_loss_weight``); a traffic mix
is ``lm_train``'s plus ``moment_dtype``.
"""

from __future__ import annotations

import importlib
import os
import types

from benchmark.families import lm_latent_hc_moe_train as streams
from benchmark.families import lm_sparse_moe_train as sparse
from benchmark.families.lm_latent_hc_moe_train import (  # noqa: F401
    STEP_FACTORY, ChoiceProbe, _corpus, _train_cfg,
    attention_kernel_flops_per_step, block_names, blocks, hyper,
    loss_at_random, make_corpus, reference_batch, reference_batch_shapes,
    tiny_batches)
from benchmark.harness import check, train_cell

# sizes small enough for the CPU (rehearsal/tiny_joyai_flash.py, the tests,
# which put them into the benchmark's own table themselves), at the published
# RATIOS: nope : rope : v = 2 : 1 : 2, q latent three times the kv latent's
# width, top-8 of 32 with 2 held (a sixteenth: 0.5 assignments a token)
TINY = {
    "config": {"hidden_size": 64, "num_attention_heads": 4,
               "num_key_value_heads": 4, "q_lora_rank": 48,
               "kv_lora_rank": 16, "qk_nope_head_dim": 16,
               "qk_rope_head_dim": 8, "v_head_dim": 16,
               "intermediate_size": 224, "moe_intermediate_size": 24,
               "n_routed_experts": 2, "num_hidden_layers": 3,
               "vocab_size": 256, "max_position_embeddings": 64,
               "dtype": "float32",
               "deployment": {"published_n_routed_experts": 32,
                              "first_expert": 0}},
    # float32 moments: the tiny sizes are float32 on both sides, and the
    # comparison reads the first gradient from Adam's first moment
    "traffic": {"seq_len": 32, "batch_per_chip": 2, "steps_per_epoch": 4,
                "remat": "none", "reference_micro_rows": 1,
                "moment_dtype": "float32"},
}


# -- required operations ------------------------------------------------------
def part_params(config: dict) -> dict:
    """Parameters of each part that take part in a matrix product once per
    token: one layer's MLA (the five projections and ``W_o``); the dense MLP;
    an expert layer's shared expert, router at its published width and the
    routed experts a token is EXPECTED to meet here (``num_experts_per_tok``
    times the share of the router's experts held, as the other routed
    families count); ``eh_proj``; the head. Norms are left out."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    kvr = config["kv_lora_rank"]
    width = config["deployment"]["published_n_routed_experts"]
    met = config["num_experts_per_tok"] * config["n_routed_experts"] / width
    expert = 3 * d * config["moe_intermediate_size"]
    return {
        "mla": (d * config["q_lora_rank"] + config["q_lora_rank"] * h * qk
                + d * (kvr + config["qk_rope_head_dim"])
                + kvr * h * (config["qk_nope_head_dim"]
                             + config["v_head_dim"])
                + h * config["v_head_dim"] * d),
        "dense_mlp": 3 * d * config["intermediate_size"],
        "expert_layer": (config["n_shared_experts"] * expert + d * width
                         + met * expert),
        "eh_proj": 2 * d * d,
        "head": d * config["vocab_size"]}


def matmul_params(config: dict) -> float:
    per, mtp = part_params(config), config["num_nextn_predict_layers"]
    dense = config["first_k_dense_replace"]
    return (blocks(config) * per["mla"] + dense * per["dense_mlp"]
            + (blocks(config) - dense) * per["expert_layer"]
            + mtp * per["eh_proj"] + (1 + mtp) * per["head"])


def required_flops_per_item(config: dict, seq: int | None = None) -> float:
    """Forward and backward FLOPs one token requires at rows of ``seq`` tokens
    (``run`` below passes the traffic's; the declared context without): 6 per
    matmul parameter, the head counted once for the main and once for the MTP
    product; every block's two S-long products counted full as the other
    families do, ``6 S heads (qk + v)``. Recomputation earns nothing."""
    s = seq or config["max_position_embeddings"]
    wide = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
            + config["v_head_dim"])
    return (6.0 * matmul_params(config)
            + blocks(config) * 6.0 * s * config["num_attention_heads"] * wide)


# -- the reference, and how its leaves lie in the program's tree ---------------
def reference_spec(config: dict) -> dict:
    from benchmark.reference import joyai_flash

    return joyai_flash.weight_spec(config)


def reference_loss(config: dict, precision: str):
    from benchmark.reference import joyai_flash

    return joyai_flash.make_loss(config, precision)


# a block's leaves without the hyper-connections', which this family lacks
_BLOCK = {path: name for path, name in streams._BLOCK.items()
          if not path[0].startswith("hc_")}


def leaf_map(config: dict) -> dict:
    """Program path -> reference key (``l<layer>.<leaf>``, the MTP module's
    ``m.<leaf>``: the reference keeps a leaf a layer, no stacks)."""
    out = dict(streams._TOP)
    if config["num_nextn_predict_layers"]:
        out.update(streams._MTP)
    for block, prefix, routed in block_names(config):
        kind = streams._ROUTED if routed else streams._DENSE
        for path, name in {**_BLOCK, **kind}.items():
            out[(block,) + path] = f"{prefix}.{name}"
    return out


# -- the program's choices, handed to the reference -----------------------------
def _seeded(config: dict, seed: int):
    """The float32 reference's weights from ``seed``, made in one call."""
    import jax

    from benchmark.harness.weights import seed_key, seeded_weights

    spec = reference_spec(config)
    return jax.jit(lambda key: seeded_weights(key, spec))(seed_key(seed))


def start_bias(config: dict, traffic: dict, seed: int, devices: list):
    """``lm_hybrid_ssm_moe_train.start_bias`` with this family's reference:
    the correction biases at balance on the corpus's first training batch at
    the seeded weights, ``[routed blocks, width]`` on the host, or None."""
    if config.get("router_bias_start", "zeros") != "balanced":
        return None
    import time

    import jax
    import numpy as np

    from benchmark.reference import joyai_flash

    t0 = time.time()
    rows = traffic["batch_per_chip"] * len(devices)
    corpus = _corpus(config, traffic, int(seed) % (2 ** 31 - 1), rows)
    tokens = np.asarray(corpus[rows:2 * rows, :-1], np.int32)
    bias = np.asarray(jax.jit(lambda w, x: joyai_flash.balanced_bias(
        w, x, config))(_seeded(config, seed), tokens))
    print(f"router_bias_start balanced: {bias.shape[0]} blocks on "
          f"{tokens.size} tokens, range by block "
          f"{[round(float(b.max() - b.min()), 4) for b in bias]}, took "
          f"{time.time() - t0:.1f} s", flush=True)
    return bias


def choice_margins(config: dict, traffic: dict, seed: int, rows,
                   bias=None) -> dict:
    """``lm_hybrid_ssm_moe_train.choice_margins`` with this family's
    reference."""
    import jax

    from benchmark.reference import joyai_flash

    margins = jax.jit(lambda w, x, b: joyai_flash.choice_margins(
        w, x, traffic["seq_len"], config, b))(_seeded(config, seed), rows,
                                              bias)
    return {k: float(v) for k, v in margins.items()}


# -- the job ------------------------------------------------------------------
def _lm_cfg(config: dict, traffic: dict):
    from ddw_tpu.utils.config import LayerSpec, LMCfg

    dep = config["deployment"]
    if config["rope_scaling"] is not None:
        raise ValueError("this family turns by plain RoPE (rope_scaling "
                         "null); lm_latent_hc_moe_train has YaRN")
    if (config["n_group"], config["topk_group"]) != (1, 1):
        raise ValueError("the program's router has no group limit: n_group "
                         "and topk_group must be 1")
    layer = LayerSpec(
        norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        bias=config["attention_bias"], attention="latent",
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        rope_scaling="", hyper_streams=0,
        mlp="swiglu", experts_per_token=config["num_experts_per_tok"],
        router_width=dep["published_n_routed_experts"],
        expert_offset=dep["first_expert"],
        norm_topk=config["norm_topk_prob"], router_score="sigmoid",
        router_scale=config["routed_scaling_factor"],
        router_bias_rate=config["router_bias_update_rate"],
        shared_expert_dim=(config["moe_intermediate_size"]
                           * config["n_shared_experts"]))
    return LMCfg(vocab_size=config["vocab_size"],
                 max_len=config["max_position_embeddings"],
                 hidden=config["hidden_size"],
                 depth=config["num_hidden_layers"],
                 num_heads=config["num_attention_heads"],
                 num_kv_heads=config["num_key_value_heads"],
                 mlp_dim=config["moe_intermediate_size"],
                 num_experts=config["n_routed_experts"], dropout=0.0,
                 dtype=config["dtype"], pos_encoding="rope",
                 remat=traffic["remat"], layer=layer,
                 dense_layers=config["first_k_dense_replace"],
                 dense_mlp_dim=config["intermediate_size"],
                 mtp_depth=config["num_nextn_predict_layers"])


def prepare(config: dict, traffic: dict, seed: int, work: str, devices: list):
    from ddw_tpu.data.prep import write_token_table
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.runtime.mesh import make_data_mesh
    from ddw_tpu.train.lm_trainer import LMTrainer

    seq = traffic["seq_len"]
    global_batch = traffic["batch_per_chip"] * len(devices)
    corpus = _corpus(config, traffic, seed, global_batch)
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


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devices: list, peaks: dict | None, **kw) -> dict:
    """``harness/train_cell.run`` with the step's own choices of experts on
    the followed steps handed to the reference and the reference's loop the
    lean one, as ``lm_latent_hc_moe_train.run``; then what only this family
    knows: the required FLOPs at the traffic's row length; the layers'
    counters that have a right value (no assignment dropped; the MTP module's
    loss near the main head's at the seeded start); how far the choices
    handed over lie from the reference's own; and how far the program's first
    gradient lies from the reference's, leaf by leaf. All judged with the
    cell's limits."""
    from benchmark.reference import optim, optim_donating, optim_lean

    tiny = kw.get("tiny") or {}
    config = dict(cell.config, **tiny.get("config", {}))
    traffic = dict(cell.traffic, **tiny.get("traffic", {}))
    # a program that cannot say this model fails here, at once, before the
    # reference's balance pass takes the chip
    _lm_cfg(config, traffic)
    spec, mapping = reference_spec(config), leaf_map(config)

    module = importlib.import_module(STEP_FACTORY[0])
    real, kept_loop = getattr(module, STEP_FACTORY[1]), optim.run_steps
    sparse._CHOSEN.clear()
    optim_donating.hold_against(None)
    controls = kw.get("controls", ())
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
    sparse.print_counters(ctx["rows"], (
        "router_bias_range", "moe_block_assignments_max", "mtp_loss"))
    nan = float("nan")      # a step without the counters is not correct
    rows = ctx["rows"]
    counted = {
        "moe_dropped": sparse._worst(r.get("moe_dropped", nan) for r in rows)}
    if config["num_nextn_predict_layers"]:
        counted["mtp_first_loss_ratio"] = (
            rows[0].get("mtp_loss", nan) / loss_at_random(config))
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
    train_cfg = _train_cfg(config, traffic, 0)
    tx = make_optimizer(train_cfg)
    step = make_lm_train_step(model, tx, mesh, seq_axis=None,
                              mtp_weight=train_cfg.mtp_weight)
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
