"""Typed config tree + CLI overrides.

The reference's configuration story is three ad-hoc idioms (SURVEY.md §5 "Config / flag
system"): module-level UPPERCASE globals per notebook
(reference ``Part 1 - Distributed Training/02_model_training_single_node.py:41-46``),
env bootstrap (``00_setup.py:3-17``), and exactly one typed dataclass, ``DataCfg``
(``Part 2 - Distributed Tuning & Inference/03_pyfunc_distributed_inference.py:85-95``).
We generalize the dataclass idiom into a small config tree with dotted-path CLI
overrides (``train.batch_size=256``), which every example script and the trainer share.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any


@dataclass
class DataCfg:
    """Dataset + preprocessing config.

    Mirrors the reference ``DataCfg``
    (``03_pyfunc_distributed_inference.py:85-95``: img height/width, batch sizes) and
    the data-prep constants (``01_data_prep.py:61-66,162``: 50% sample, 90/10 split,
    seed 42).
    """

    table_root: str = "/tmp/ddw_tpu/tables"
    source_dir: str = ""                # raw JPEG class-dir tree (tf_flowers layout)
    img_height: int = 224
    img_width: int = 224
    channels: int = 3
    sample_fraction: float = 0.5        # reference samples 50% of the raw images
    train_fraction: float = 0.9         # 90/10 split
    split_seed: int = 42                # reference seed
    shard_size: int = 256               # records per shard file in the table store
    shuffle_buffer: int = 1024
    prefetch: int = 2                   # host->device double buffering depth
    loader_workers: int = 4             # decode thread pool (petastorm workers_count role)

    @property
    def image_shape(self) -> tuple[int, int, int]:
        return (self.img_height, self.img_width, self.channels)


@dataclass
class ModelCfg:
    """Model factory config.

    The reference model: MobileNetV2 ImageNet-pretrained frozen base + GAP ->
    Dropout(0.5) -> Dense(num_classes) head
    (``02_model_training_single_node.py:159-178``).
    """

    name: str = "mobilenet_v2"          # key into ddw_tpu.models.registry
    num_classes: int = 5
    dropout: float = 0.5
    freeze_base: bool = True            # transfer-learning mode: only the head trains
    width_mult: float = 1.0
    num_heads: int = 0                  # attention heads (ViT); 0 = model default.
                                        # Param shapes depend on it — set it when
                                        # restoring a package saved with a
                                        # non-default head count.
    hidden: int = 0                     # encoder width (ViT); 0 = model default
                                        # (192). The v5e MXU is a 128x128 array:
                                        # hidden=256 with num_heads=2 puts every
                                        # projection and attention dot on full
                                        # 128-wide tiles (tools/mxu_roofline.py
                                        # quantifies the default's 59% ceiling).
                                        # Param shapes depend on it — set it when
                                        # restoring a non-default package.
    pretrained_path: str = ""           # optional converted-weights artifact
    allow_frozen_random: bool = False   # opt-in: keep freeze_base=True even with
                                        # no pretrained_path (build_model otherwise
                                        # auto-unfreezes — a frozen random backbone
                                        # trains the head over noise). For
                                        # mechanism tests and throughput benches.
    bn_momentum: float = 0.9            # BatchNorm running-stat momentum. Default
                                        # 0.9 suits short from-scratch runs; set
                                        # 0.99 (the Keras MobileNetV2 value) for
                                        # parity runs finetuning an unfrozen
                                        # pretrained base.
    dtype: str = "bfloat16"             # compute dtype on the MXU; params stay f32
    stem_s2d: bool = False              # compute the stride-2 stem conv via 2x2
                                        # space-to-depth (identical math, same
                                        # params; deepens the MXU contraction
                                        # over the 3-channel image input).
                                        # CNN families only (mobilenet/resnet).
    dw_impl: str = "xla"                # depthwise-conv implementation for the
                                        # MobileNet family: "xla" grouped conv
                                        # or "pallas" (in-tree VMEM-resident
                                        # kernel, ddw_tpu.ops.depthwise_conv;
                                        # stride-2 layers stay on XLA)
    lora_rank: int = 0                  # >0 (ViT): rank-r LoRA adapters on
                                        # lora_targets; the trainer freezes
                                        # everything but adapters+head
                                        # (mutually exclusive w/ freeze_base)
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")


@dataclass
class TrainCfg:
    """Training loop + distribution config.

    Mirrors the single-node constants (batch 32, 3 epochs, Adam 1e-3,
    ``02_model_training_single_node.py:45-46,201-203``) and the distributed contract
    (batch 256/worker, LR x world, 5-epoch warmup, plateau patience 10,
    ``03_model_training_distributed.py:81-82,301,318-321``).
    """

    batch_size: int = 32                # per-worker batch (reference semantics)
    epochs: int = 3
    optimizer: str = "adam"             # adam | adamw | adadelta | sgd
                                        # (HPO space includes Adadelta)
    learning_rate: float = 1e-3
    weight_decay: float = 0.0           # adamw decoupled weight decay
    grad_clip_norm: float = 0.0         # >0: clip grads by global norm before
                                        # the optimizer update
    scale_lr_by_world: bool = True      # Adam(0.001 * hvd.size()) semantics
    warmup_epochs: int = 5              # LearningRateWarmupCallback(warmup_epochs=5)
    plateau_patience: int = 10          # ReduceLROnPlateau(patience=10)
    plateau_factor: float = 0.5
    lr_schedule: str = "plateau"        # "plateau" (reference semantics) or
                                        # "cosine" (per-batch half-cycle decay
                                        # after warmup; plateau callback off)
    cosine_final_lr_frac: float = 0.0   # cosine floor as a fraction of the
                                        # scaled target LR
    ema_decay: float = 0.0              # >0: Polyak shadow of the params in
                                        # the opt state (train/step.EmaState);
                                        # the trainer evaluates with the
                                        # shadow; read it via
                                        # ddw_tpu.train.step.ema_params
    early_stop_patience: int = 0        # 0 = disabled; pyfunc notebook uses 3
    seed: int = 0
    grad_accum_steps: int = 1           # >1: split each per-worker batch into N
                                        # sequential microbatches inside the jitted
                                        # step (lax.scan), accumulating gradients —
                                        # same optimizer math, 1/N activation
                                        # memory; batches far beyond HBM fit.
    steps_per_dispatch: int = 1         # >1: fuse K optimizer steps into ONE
                                        # jitted program (lax.scan over a
                                        # stacked [K, B, ...] super-batch the
                                        # loader assembles on device;
                                        # train/step.make_train_chain) — ~1/K
                                        # the host dispatches and metric
                                        # fetches; same training result.
                                        # Fault hooks, preemption checks and
                                        # per-batch LR writes move to chain
                                        # boundaries (docs/performance.md).
                                        # Composes with grad_accum_steps and
                                        # zero/fsdp; refused with
                                        # pipeline_stages (the pipeline step
                                        # already fuses its microbatches).
    moment_dtype: str = "float32"       # "bfloat16": store Adam/SGD first
                                        # moments (mu) in bf16 — halves mu
                                        # bytes; nu stays f32 (feeds rsqrt).
                                        # adadelta refuses (both its
                                        # accumulators are nu-like)
    mtp_weight: float = 0.1             # the multi-token-prediction term's
                                        # weight in the loss that is descended
                                        # (LMCfg.mtp_depth > 0; DeepSeek-V3's
                                        # late-training value); ``loss`` stays
                                        # the main head's cross-entropy
    exit_entropy_weight: float = 0.05   # beta of the loss a model with
                                        # LMCfg.exit_gate descends: the mean
                                        # over tokens of sum_t p_t CE_t -
                                        # beta H(p), p a token's distribution
                                        # over the exits (arXiv:2510.25741)
    data_axis: str = "data"             # mesh axis name for DP psum
    num_devices: int = 0                # 0 = all visible devices
    zero: bool = False                  # ZeRO-1: shard optimizer moments over
                                        # the data axis (parallel/zero.py);
                                        # checkpoints switch to the sharded
                                        # per-process format (no full gather).
                                        # Composes with grad_accum_steps and
                                        # with async_checkpoint (per-process
                                        # background writers run the same
                                        # collective commit protocol).
    fsdp: bool = False                  # ZeRO-3/FSDP: shard params AND
                                        # optimizer state over the data axis
                                        # (~1/N model residency per device;
                                        # GSPMD inserts per-layer all-gathers).
                                        # Same checkpoint format and flag
                                        # incompatibilities as zero; zero and
                                        # fsdp are mutually exclusive.
    pipeline_stages: int = 0            # >0: LMTrainer trains the LM through
                                        # the pipeline step (parallel/
                                        # pipeline.py) over a (data, pipe)
                                        # mesh — pipe=stages, data absorbs
                                        # the remaining devices. Requires
                                        # lm.dropout == 0 and divides depth.
    pipeline_schedule: str = "gpipe"    # "gpipe" | "interleaved" (virtual
                                        # stages; ~v-fold smaller bubble,
                                        # microbatches <= stages)
    pipeline_microbatches: int = 4      # must divide the per-replica batch
    pipeline_virtual_stages: int = 2    # interleaved only: chunks per device
    checkpoint_dir: str = ""            # "" = no per-epoch checkpoints
    async_checkpoint: bool = False      # serialize+write checkpoints on a
                                        # background thread (device snapshot is
                                        # still synchronous) so IO overlaps the
                                        # next epoch's compute; works for the
                                        # classic AND the sharded (zero/fsdp)
                                        # formats
    async_checkpoint_inflight: int = 2  # bounded async write queue depth: a
                                        # save blocks only past this many
                                        # outstanding writes, so one slow
                                        # fsync never stalls a chain boundary
                                        # (1 = join-previous-before-new)
    checkpoint_every_epochs: int = 1
    checkpoint_keep_best: bool = False  # also keep the single best-val_loss
                                        # state under <checkpoint_dir>/best
                                        # (model selection; the resume stream's
                                        # newest-K retention would prune it)
    log_every_steps: int = 10
    trace_dir: str = ""                 # --trace flag role, SURVEY §5: profile of
                                        # the first settled epoch + span tree
    debug_cross_host_checks: bool = False  # SPMD consistency sanitizer, SURVEY §5
    monitor_interval_s: float = 0.0     # >0: sys.* utilization sampler into the
                                        # tracker (Ganglia role, SURVEY §5)


@dataclass(frozen=True)
class LayerSpec:
    """What one decoder layer is made of (ROADMAP D3): the kind of norm, of
    attention and of MLP, and the sizes each kind reads. One object that
    ``TransformerLM`` hands down to its blocks and their attention, in place
    of a field re-declared on each of the three. The default is the block the
    LM family always had (float32 LayerNorm, biases, full causal attention at
    ``hidden // num_heads`` a head, GELU), parameter names included.
    """

    norm: str = "layernorm"             # "layernorm" | "rmsnorm", in float32
    norm_eps: float = 1e-6
    bias: bool = True                   # on every projection, MLP and the head
    post_norm: bool = False             # a second norm on each sublayer's
                                        # OUTPUT, before the residual add
                                        # (sandwich norm): x + norm(f(norm(x)))
    head_dim: int = 0                   # 0: hidden // num_heads
    qk_norm: bool = False               # RMSNorm over each head of q and of k
    rope_theta: float = 10000.0
    mrope_section: tuple[int, ...] = ()  # frequency pairs a position
                                        # component (temporal, height, width);
                                        # (): one component, plain RoPE
    attention: str = "full"             # "full" | "indexed": a lightning
                                        # indexer scores every causal key and
                                        # each query attends to its index_topk
                                        # best (ops/indexed_attention.py) |
                                        # "latent": low-rank q and kv paths
                                        # with a norm on each latent, one
                                        # rotary key head shared by all query
                                        # heads (MLA, DeepSeek-V2)
    q_lora_rank: int = 0                # latent: the query latent's width
    kv_lora_rank: int = 0               # latent: the key/value latent's width
    qk_nope_dim: int = 0                # latent: a q/k head's part without
    qk_rope_dim: int = 0                # positions, and its rotary part
    v_head_dim: int = 0                 # latent: a value head's width
    rope_scaling: str = ""              # "" | "yarn" (ops/rope.py yarn_angles:
                                        # blended frequencies, and the latent
                                        # attention's softmax scale times
                                        # (0.1 ln rope_factor + 1)^2)
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_len: int = 0          # the context the factor stretches
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_tile: int = 512               # queries a tile of the scores
    mlp: str = "gelu"                   # "gelu" (fc1, fc2) | "swiglu" (gate,
                                        # up, down) | "relu2" (up, down:
                                        # relu(x)^2, routed and shared experts
                                        # only); the experts' too
    # routed MLP without dropped tokens (models/moe.py RoutedExperts), taken
    # when LMCfg.num_experts > 0 and experts_per_token > 0; num_experts is
    # then the number this chip HOLDS and mlp_dim one expert's width
    experts_per_token: int = 0          # 0: moe_router's capacity dispatch
    router_width: int = 0               # experts the router scores: the whole
                                        # deployment's; 0 = num_experts
    expert_offset: int = 0              # first expert held here
    norm_topk: bool = True              # chosen weights renormalised to 1
    embed_scale: float = 1.0            # token embeddings times this (the
                                        # original Transformer's and Gemma's
                                        # sqrt(hidden)); a trained table can
                                        # fold it in
    router_score: str = "softmax"       # "softmax" over router_width | a
                                        # "sigmoid" an expert (DeepSeek-V3)
    router_scale: float = 1.0           # the chosen weights times this
    router_bias_rate: float = 0.0       # > 0: a correction bias an expert that
                                        # only the choice sees, no gradient,
                                        # moved after each step by this much
                                        # towards an even load (models/moe.py
                                        # step_router_bias)
    shared_expert_dim: int = 0          # > 0: an expert of this width that
                                        # every token takes, beside the routed
    # the Mamba-2 mixer of an "M" layer (models/mamba.py, ops/ssd.py)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1                 # groups of heads that share B and C
    ssm_state: int = 0                  # N, a head's state is head_dim x N
    ssm_conv: int = 4                   # taps of the causal depthwise conv
    ssm_chunk: int = 128                # tokens a chunk of the scan
    ssm_dt_shift: float = 0.0           # added to dt_bias inside the softplus;
                                        # a trained bias can fold it in
    # manifold-constrained hyper-connections (arXiv:2512.24880) around both
    # sublayers of a DecoderBlock (models/lm.py HyperConnection)
    hyper_streams: int = 0              # 0 | 1: the residual h + f(norm(h));
                                        # n > 1: n streams [B, S, n, C], read
                                        # by a learnt mix, written back to all
                                        # and mixed by a doubly stochastic
                                        # matrix a token and sublayer
    hyper_sinkhorn_iters: int = 20      # rounds of (rows, then columns)
    hyper_eps: float = 1e-6             # added to each sum a round divides by
    hyper_res_clamp: tuple[float, float] = (-30.0, 30.0)  # before the exp
    hyper_res_diag: float = 0.0         # added to the diagonal of the mixing
                                        # matrix's logits; a trained b_res can
                                        # fold it in

    @property
    def sows(self) -> bool:
        """Whether a layer of this spec sows a loss term or counters."""
        return (self.attention == "indexed" or self.experts_per_token > 0
                or self.ssm_heads > 0 or self.hyper_streams > 1)


@dataclass
class LMCfg:
    """Decoder-only LM config (:class:`ddw_tpu.models.lm.TransformerLM`).

    Not a reference-parity item (the reference has no language model — SURVEY.md
    §5 "Long-context ... Absent"); this is the long-context model family, trained
    via the DPxSP step in :mod:`ddw_tpu.train.lm_step`.
    """

    vocab_size: int = 256
    max_len: int = 2048                 # global sequence length bound
    hidden: int = 256
    depth: int = 4
    num_heads: int = 4
    mlp_dim: int = 1024
    dropout: float = 0.0
    dtype: str = "bfloat16"
    num_experts: int = 0                # >0: MoE MLP blocks
    capacity_factor: float = 1.25       # static expert capacity = cf*k*T/E
    moe_router: str = "top1"            # "top1" (Switch) or "top2" (GShard:
                                        # two experts/token, renormalized
                                        # pair gates)
    num_kv_heads: int = 0               # GQA: KV heads (0 = num_heads / MHA).
                                        # Shrinks k/v params and the decode
                                        # KV cache by num_heads/num_kv_heads;
                                        # K/V broadcast per query group at
                                        # compute
    lora_rank: int = 0                  # >0: rank-r LoRA adapters on
                                        # lora_targets (ddw_tpu.models.lora);
                                        # train with lora_optimizer so only
                                        # adapters (+head) update
    lora_alpha: float = 16.0
    lora_targets: tuple[str, ...] = ("query", "value")
    pos_encoding: str = "learned"       # "learned" absolute table, "rope"
                                        # rotary relative positions
                                        # (ddw_tpu.ops.rope — extrapolates
                                        # past max_len, SP/decode-composable)
                                        # or "none" (the position lives in
                                        # the pattern's state-space layers)
    pattern: str = ""                   # one mixer a layer, a character each:
                                        # "M" Mamba-2, "E" experts, "*"
                                        # attention (h + mixer(norm(h)));
                                        # depth = its length. "": every layer
                                        # an attention and an MLP
    dense_layers: int = 0               # leading layers whose MLP is the dense
                                        # one at dense_mlp_dim where the rest
                                        # route (num_experts > 0)
    dense_mlp_dim: int = 0              # the leading dense layers' MLP width
    mtp_depth: int = 0                  # 0 | 1: a multi-token-prediction
                                        # module after the trunk (DeepSeek-V3)
                                        # predicts the token after next
                                        # through the shared embedding and
                                        # head; TrainCfg.mtp_weight its term
    passes: int = 1                     # > 1: the stack of depth blocks and
                                        # the final norm run this many times
                                        # over ONE set of weights, the normed
                                        # state of a pass the next one's input
                                        # (a looped decoder); the head reads
                                        # the last pass
    exit_gate: bool = False             # an exit after every pass through the
                                        # one head, and a learned gate (hidden
                                        # -> 1, sigmoid) a token and pass that
                                        # spreads the token over the exits;
                                        # training descends the expected loss
                                        # over them (TrainCfg.
                                        # exit_entropy_weight); logits, loss
                                        # and accuracy stay the last exit's
    remat: str = "none"                 # per-block activation remat: "full"
                                        # (keep nothing; recompute block in
                                        # bwd) or "dots" (keep matmul outputs)
                                        # — long contexts past HBM at ~1/3
                                        # more FLOPs; decode unaffected
    layer: LayerSpec = field(default_factory=LayerSpec)

    def __post_init__(self):
        if isinstance(self.layer, dict):    # from a package's JSON
            self.layer = LayerSpec(**{
                k: tuple(v) if isinstance(v, list) else v
                for k, v in self.layer.items()})


@dataclass
class TuneCfg:
    """Hyperparameter-search config.

    Mirrors fmin(max_evals=20, SparkTrials(parallelism=4))
    (``01_hyperopt_single_machine_model.py:226-238``) and the sequential distributed
    mode (``02_hyperopt_distributed_model.py:341-365``).
    """

    max_evals: int = 20
    parallelism: int = 4                # >1 = parallel trial executor; 1 = sequential
    seed: int = 0
    algo: str = "tpe"                   # tpe | random
    n_startup_trials: int = 5           # random trials before TPE kicks in
    gamma: float = 0.25                 # TPE good/bad split quantile
    prune: bool = False                 # trial pruning (beyond hyperopt):
                                        # stop hopeless trials early on their
                                        # per-epoch val_loss
    pruner: str = "median"              # "median" (Vizier/Optuna rule) or
                                        # "asha" (async successive halving)
    prune_warmup_epochs: int = 1        # median: never prune below this epoch
    prune_min_trials: int = 3           # median: peers needed before trusted
    asha_min_resource: int = 1          # asha: first rung (epochs)
    asha_reduction_factor: int = 3      # asha: eta — top 1/eta survive a rung


_TYPES = {"data": DataCfg, "model": ModelCfg, "train": TrainCfg, "tune": TuneCfg,
          "lm": LMCfg}


def require_tpu_or_exit(verb: str = "measure") -> str:
    """The opt-in refusal of the offline measurement tools (``tools/``): with
    ``DDW_REQUIRE_TPU`` set and a backend that is not a TPU, print the refusal
    to stderr and exit 4. Returns the device kind. ``chip_smoke.py`` does not
    use it — it refuses a non-TPU platform unconditionally."""
    import sys

    import jax

    dev = jax.devices()[0]
    if env_flag("DDW_REQUIRE_TPU") and dev.platform != "tpu":
        print(f"DDW_REQUIRE_TPU is set but the JAX platform is "
              f"{dev.platform!r} ({dev.device_kind}); refusing to {verb}",
              file=sys.stderr)
        sys.exit(4)
    return dev.device_kind


def env_flag(name: str) -> bool:
    """Boolean environment flag of the perf tools (``tools/``).

    Accepts the common spellings both ways; anything else raises — a typo
    must not silently flip a flag in either direction (enabling
    DDW_BENCH_SMOKE degrades measurements; disabling DDW_REQUIRE_TPU records
    CPU timings as chip results)."""
    import os

    val = os.environ.get(name, "").strip().lower()
    if val in ("", "0", "false", "no", "off"):
        return False
    if val in ("1", "true", "yes", "on"):
        return True
    raise ValueError(f"{name} must be a boolean flag "
                     f"(1/true/yes/on or 0/false/no/off), got {val!r}")


def apply_overrides(cfgs: dict[str, Any], overrides: list[str]) -> dict[str, Any]:
    """Apply ``section.key=value`` CLI overrides to a dict of config dataclasses.

    Values parse as JSON when possible (``train.batch_size=256`` -> int), else string.
    """
    for ov in overrides:
        if "=" not in ov or "." not in ov.split("=", 1)[0]:
            raise ValueError(f"override must look like section.key=value, got {ov!r}")
        path, raw = ov.split("=", 1)
        section, key = path.split(".", 1)
        if section not in cfgs:
            raise KeyError(f"unknown config section {section!r} (have {sorted(cfgs)})")
        cfg = cfgs[section]
        if not hasattr(cfg, key):
            raise KeyError(f"{type(cfg).__name__} has no field {key!r}")
        try:
            val = json.loads(raw)
        except json.JSONDecodeError:
            val = raw
        setattr(cfg, key, val)
    return cfgs


def to_dict(cfg: Any) -> dict[str, Any]:
    """Flatten a dataclass config to a JSON-able dict (for tracker param logging)."""
    return dataclasses.asdict(cfg)


def default_cfgs() -> dict[str, Any]:
    return {name: typ() for name, typ in _TYPES.items()}
