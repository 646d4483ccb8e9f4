"""Benchmark matrix: throughput + MFU for the framework's headline workloads.

Workloads (BASELINE.md "Metrics to record per config"; reference publishes no
numbers — absence documented in BASELINE.md "Published numbers"):

- ``mobilenet_v2_frozen``  — the reference's transfer contract (frozen base,
  224², batch 256, Adam, sparse CE; ``02_model_training_single_node.py:159-178``);
- ``mobilenet_v2_unfrozen`` — same model, full backward;
- ``resnet50``             — the heavy conv family, full backward;
- ``vit``                  — in-tree Pallas flash-MHA path (``models/vit.py``);
- ``lm_flash``             — decoder LM, causal auto-dispatch attention, seq 2048;
- ``lm_moe``               — same LM with Switch top-1 MoE MLPs (8 experts,
  dense on one chip; EP's all_to_alls need a mesh — see dryrun).

Each row reports images(or tokens)/sec/chip, median step time, the XLA-counted
FLOPs of the compiled step (``Compiled.cost_analysis()['flops']`` — the actual
executed program: forward + backward + optimizer update), the achieved TFLOP/s,
and MFU against the chip's bf16 peak. MFU here is *hardware* FLOP utilization of
the whole train step, not the analytical 6ND convention — it is directly
defensible because both numerator (XLA's own FLOP count) and denominator
(published chip peak) are external to this code.

Timing discipline: chained donated steps, with completion forced by a
device-to-host fetch of the final step's scalar loss. The per-step time is
``(T(2N) - T(N)) / N`` — the difference cancels the fixed dispatch + fetch
latency — with N grown adaptively until the differential is >= ~1 s of device
work, then the median over ``REPEATS`` differentials. (Whether the plain
``block_until_ready`` form is as good on the sealed chip machine is ROADMAP
S1's to settle; the method is unchanged here.)

Also measures the host input pipeline (SURVEY.md §7 hard-part 3): native C++
JPEG decode rate vs PIL vs the device step rate, answering "is the chip ever
starved at batch 256?".

Prints ONE JSON line. Headline fields ({"metric", "value", "unit",
"vs_baseline"}) keep the round-1 contract — frozen-MobileNetV2 images/sec/chip
vs the TPU v5e anchor — and the full matrix rides along under
"configs" / "host_pipeline" / "device". A config that raises lands in the
matrix as ``{"error": ...}``; the line is still printed and the exit status is
1. Full shapes run on a TPU only (exit 1 naming the platform found otherwise).

Env: ``DDW_BENCH_SMOKE=1`` shrinks every shape/step count for CPU CI;
``DDW_BENCH_ONLY=name1,name2`` restricts the matrix;
``DDW_BENCH_CHAIN=loop|scan|K`` picks the dispatch arm — ``K`` (an int >= 2)
measures the fused K-step chain (``TrainCfg.steps_per_dispatch``) AND the
host-loop arm on the same compiled step, reporting the per-step
dispatch-overhead delta the chain amortizes (``dispatch_overhead_ms_per_step``).
"""

import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# Anchor for vs_baseline: the round-2 measurement on one TPU v5e chip, taken
# through the previous backend (rounds 2-5; its records were removed in PR 21).
# ROADMAP S1 replaces the anchors with ledger numbers from the sealed machine.
BASELINE_IPS = 40030.89  # round-2 anchor, TPU v5e-1, 2026-07-29

# Per-config chip anchors (value + the round it was measured, previous
# backend) — each full-shape TPU row also reports vs_anchor/anchor_round.
CHIP_ANCHORS = {
    "mobilenet_v2_frozen": (BASELINE_IPS, 2),
    "mobilenet_v2_frozen_feature_cache": (113000.0, 3),
    "mobilenet_v2_unfrozen": (4616.0, 2),
    "resnet50": (2023.0, 2),
    "vit": (7829.0, 2),
    "lm_flash": (129639.0, 2),
}

# Tile-quantized MFU ceilings for the transformer rows (tools/mxu_roofline
# .py, round 5): the 128x128 MXU caps these shapes well below peak (ViT's
# head_dim-48 attention dots run at 28% tile utilization), so each full-shape
# row reports mfu alongside the ceiling its own shapes can actually reach —
# mfu/mfu_ceiling is the implementation gap, not mfu/1.0.
MFU_CEILINGS = {
    "vit": 0.59,
    "lm_flash": 0.71,
}

from ddw_tpu.utils.compile_cache import enable_compile_cache
from ddw_tpu.utils.config import env_flag

SMOKE = env_flag("DDW_BENCH_SMOKE")
REPEATS = 1 if SMOKE else 3
# Adaptive sizing: grow N until one differential run holds >= this much device
# work, so fixed dispatch/fetch latency stays inside the noise floor.
MIN_MEASURE_S = 0.05 if SMOKE else 1.0
MAX_STEPS = 8 if SMOKE else 1024

# bf16 peak TFLOP/s per *jax device* (chip for v4+, core for v2/v3); public
# spec-sheet numbers. At full shapes a device that is not in the table is an
# error, not a row with mfu=null.
PEAK_BF16_TFLOPS = {
    "TPU v2": 22.5,
    "TPU v3": 61.5,
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def _device_peak_tflops() -> tuple[str, float | None]:
    """(device_kind, bf16 peak). The CPU smoke has no peak (``None``); any
    other device must be in the table."""
    kind = jax.devices()[0].device_kind
    for key, peak in PEAK_BF16_TFLOPS.items():
        if kind.lower().startswith(key.lower()):
            return kind, peak
    if SMOKE:
        return kind, None
    raise ValueError(f"no bf16 peak on file for device_kind {kind!r}; add it "
                     f"to PEAK_BF16_TFLOPS with its source")


def _compiled_flops(lowered_compiled) -> float:
    """Total FLOPs of one executed step, from XLA's own cost model."""
    return float(lowered_compiled.cost_analysis()["flops"])


def _log(note: str) -> None:
    print(f"[bench] {note}", file=sys.stderr, flush=True)


_CHAIN_RAW = os.environ.get("DDW_BENCH_CHAIN", "loop")
if _CHAIN_RAW in ("loop", "scan"):
    CHAIN = _CHAIN_RAW
else:
    # Integer K: the fused K-step dispatch A/B arm (steps_per_dispatch) —
    # a lax.scan over K steps fed by a stacked super-batch with state +
    # super-batch donation, PLUS a host-loop measurement of the same
    # compiled step so each row reports the measured per-step dispatch
    # overhead the chain amortizes.
    try:
        CHAIN = int(_CHAIN_RAW)
    except ValueError:
        raise ValueError(f"DDW_BENCH_CHAIN must be 'loop', 'scan', or an "
                         f"integer K >= 2, got {_CHAIN_RAW!r}") from None
    if CHAIN < 2:
        raise ValueError(f"DDW_BENCH_CHAIN=K needs K >= 2 (K=1 IS the loop "
                         f"arm), got {CHAIN}")
SCAN_CHUNK = 2 if SMOKE else 8


def _host_loop_runner(compiled, holder, args, next_batch=None):
    """The per-step host-dispatch ``run_n`` over ``holder['state']`` — the
    'loop' arm, and the A/B reference the DDW_BENCH_CHAIN=K arm times against
    (same AOT-compiled step, same state stream)."""
    def run_n(n):
        st = holder["state"]
        t0 = time.perf_counter()
        for _ in range(n):
            a = (*next_batch(), *args) if next_batch else args
            st, m = compiled(st, *a)
        np.asarray(m["loss"])  # forced D2H: true completion barrier
        holder["state"] = st
        return time.perf_counter() - t0

    return run_n


def _chained_runner(step, compiled, state, args, next_batch=None):
    """Build ``run_n`` for :func:`_time_steps` over a train step.

    ``next_batch()`` (optional) supplies fresh leading step arguments per
    step — the loader-fed e2e rows. Those rows are host-loop by construction
    (a lax.scan cannot pull host batches), so ``next_batch`` forces the loop
    arm whatever ``DDW_BENCH_CHAIN`` says.

    ``DDW_BENCH_CHAIN=loop`` (default) dispatches every step from the host —
    steps pipeline asynchronously, so the device starves only when the host's
    dispatch rate drops below the device's step rate. ``=scan`` compiles a
    ``lax.scan`` over ``SCAN_CHUNK`` steps so ONE dispatch covers CHUNK steps
    of device work: short-step rows (frozen MobileNetV2 ~6 ms, feature-cache
    ~2 ms) can be dispatch-bound under 'loop' while 'scan' still measures
    device throughput — running both separates the two.
    ``=K`` (an int >= 2) measures the fused K-step dispatch mode
    (``TrainCfg.steps_per_dispatch``): a scan over K steps fed by a stacked
    ``[K, ...]`` super-batch (rebuilt per chain by a device-side stack, as
    the training loader does), with state + super-batch donated — and ALSO
    times the host-loop arm so the row reports the dispatch overhead the
    chain amortizes (``_chain_ab_fields``).

    ``step`` must be the traceable (jitted) step — the AOT ``compiled`` one
    cannot be called under tracing and serves the 'loop' arm + FLOP count.
    """
    holder = {"state": state}
    if CHAIN == "loop" or next_batch is not None:
        return _host_loop_runner(compiled, holder, args, next_batch)

    if CHAIN == "scan":
        def mega(st, *a):
            def body(c, _):
                c2, m = step(c, *a)
                return c2, m["loss"]

            st2, losses = jax.lax.scan(body, st, None, length=SCAN_CHUNK)
            return st2, losses[-1]

        mega_c = jax.jit(mega, donate_argnums=(0,))
        st, last = mega_c(holder["state"], *args)  # warmup/compile
        np.asarray(last)
        _log("scan megastep: compiled")
        holder["state"] = st

        def run_n(n):
            assert n % SCAN_CHUNK == 0, (n, SCAN_CHUNK)
            st = holder["state"]
            t0 = time.perf_counter()
            for _ in range(n // SCAN_CHUNK):
                st, last = mega_c(st, *args)
            np.asarray(last)  # forced D2H: true completion barrier
            holder["state"] = st
            return time.perf_counter() - t0

        run_n.chunk = SCAN_CHUNK
        return run_n

    # CHAIN = int K: fused K-step dispatch. Convention across the synthetic
    # rows: args = (*per-step batch arrays, rng) — the batches stack to
    # [K, ...] super-batches (consumed/donated per chain, re-stacked on
    # device each call exactly as the training loader assembles them), the
    # rng stays chain-static (the step folds state.step itself).
    k = CHAIN
    batch, static = args[:-1], args[-1:]
    stack_k = jax.jit(
        lambda g: jax.tree.map(lambda x: jnp.stack([x] * k), g))

    def chain_fn(st, stacked, *stat):
        def body(c, xs):
            c2, m = step(c, *xs, *stat)
            return c2, m["loss"]

        st2, losses = jax.lax.scan(body, st, stacked)
        return st2, losses[-1]

    chain_c = jax.jit(chain_fn, donate_argnums=(0, 1))
    st, last = chain_c(holder["state"], stack_k(batch), *static)  # warmup
    np.asarray(last)
    _log(f"chain megastep (K={k}): compiled")
    holder["state"] = st

    def run_n(n):
        assert n % k == 0, (n, k)
        st = holder["state"]
        t0 = time.perf_counter()
        for _ in range(n // k):
            st, last = chain_c(st, stack_k(batch), *static)
        np.asarray(last)  # forced D2H: true completion barrier
        holder["state"] = st
        return time.perf_counter() - t0

    run_n.chunk = k
    run_n.chain_k = k
    run_n.loop_run = _host_loop_runner(compiled, holder, args)
    return run_n


def _chain_ab_fields(run_n, dt: float, measured_steps: int) -> dict:
    """For the DDW_BENCH_CHAIN=K arm: time the host-loop arm on the same
    compiled step/state stream and report the measured per-step host-overhead
    delta the fused chain amortizes. Empty for the loop/scan arms."""
    k = getattr(run_n, "chain_k", None)
    if not k:
        return {}
    chain_ms = dt / measured_steps * 1e3
    ldt, ln = _time_steps(run_n.loop_run)
    _log(f"chain A/B: loop arm measured ({ln} steps)")
    loop_ms = ldt / ln * 1e3
    return {"chain_k": k,
            "loop_step_time_ms": round(loop_ms, 4),
            "dispatch_overhead_ms_per_step": round(loop_ms - chain_ms, 4)}


def _time_steps(run_n) -> tuple[float, int]:
    """True seconds-per-``N``-steps of device work, via differential timing.

    ``run_n(n)`` must run ``n`` chained steps and FORCE completion with a
    device-to-host fetch (``np.asarray`` of a scalar output). The differential
    ``T(2N) - T(N)`` cancels the fixed dispatch+fetch latency; N doubles until
    the differential holds >= MIN_MEASURE_S of device work. Returns (median
    differential seconds, N) — i.e. the time N steps take.
    """
    n = 2 if SMOKE else 8
    chunk = getattr(run_n, "chunk", 1)
    if chunk > 1:
        # Scan/chain runners execute whole megasteps, so n must be a multiple
        # of the runner's chunk (SCAN_CHUNK or the chain K). Round up here
        # (doubling preserves it).
        n = -(-n // chunk) * chunk
    while True:
        dt = run_n(2 * n) - run_n(n)
        if dt >= MIN_MEASURE_S or n >= MAX_STEPS:
            break
        n *= 2
    times = [dt]
    for _ in range(REPEATS - 1):
        times.append(run_n(2 * n) - run_n(n))
    good = [t for t in times if t > 0]
    return (statistics.median(good) if good else run_n(n)), n


def _row(items_per_step: int, n_chips: int, dt: float, measure_steps: int,
         flops: float | None, peak: float | None, unit: str) -> dict:
    rate = measure_steps * items_per_step / dt
    step_ms = dt / measure_steps * 1e3
    out = {
        "rate_per_chip": round(rate / n_chips, 2),
        "unit": unit,
        "step_time_ms": round(step_ms, 4),
        "step_flops": flops,
        "achieved_tflops_per_chip": None,
        "mfu": None,
    }
    if flops:
        tf = flops / dt * measure_steps / n_chips / 1e12
        out["achieved_tflops_per_chip"] = round(tf, 6)
        if peak:
            out["mfu"] = round(tf / peak, 6)
    if CHAIN != "loop":
        out["chain"] = CHAIN  # scan-chained timing (see _chained_runner)
    return out


def bench_vision(model_name: str, *, freeze_base: bool, batch: int,
                 img: tuple, peak: float | None) -> dict:
    from ddw_tpu.models.registry import build_model
    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS
    from ddw_tpu.train.step import (batch_sharding, init_state, make_train_step,
                                    replicated_sharding)
    from ddw_tpu.utils.config import ModelCfg, TrainCfg

    devices = jax.devices()
    n_chips = len(devices)
    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)), devices=devices)

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # frozen-random warning: bench measures speed
        # A/B knob for the space-to-depth stem (identical math; see
        # ddw_tpu/ops/s2d_conv.py). CNN families only — ViT has no stem conv
        # in this sense and its builder ignores the flag.
        s2d = (os.environ.get("DDW_BENCH_S2D", "0").lower()
               not in ("0", "", "false", "no")
               and model_name.startswith(("mobilenet", "resnet")))
        # DDW_BENCH_DW=pallas routes MobileNet's stride-1 depthwise layers
        # through the in-tree Pallas kernel (ddw_tpu/ops/depthwise_conv.py).
        dw = os.environ.get("DDW_BENCH_DW", "xla")
        if dw not in ("xla", "pallas"):  # a typo must not silently bench XLA
            raise ValueError(f"DDW_BENCH_DW must be 'xla' or 'pallas', got {dw!r}")
        if not model_name.startswith("mobilenet"):
            dw = "xla"
        # A/B knobs for the tile-aligned ViT arm (ab_vit_tile): the default
        # h192/H4 geometry runs head_dim-48 attention dots at 28% MXU tile
        # utilization and caps the row at 59% MFU (tools/mxu_roofline.py);
        # h256/H2 puts every dot on full 128-wide tiles. ViT only — the conv
        # families have no head geometry.
        from ddw_tpu.utils.config import vit_geometry_env

        vit_kw = vit_geometry_env() if model_name == "vit" else {}
        model_cfg = ModelCfg(name=model_name, num_classes=5, dropout=0.5,
                             freeze_base=freeze_base, dtype="bfloat16",
                             allow_frozen_random=freeze_base, stem_s2d=s2d,
                             dw_impl=dw, **vit_kw)
        model = build_model(model_cfg)
    train_cfg = TrainCfg(batch_size=batch, optimizer="adam", learning_rate=1e-3)
    state, tx = init_state(model, model_cfg, train_cfg, img, jax.random.PRNGKey(0))
    step = make_train_step(model, tx, mesh, DATA_AXIS, donate=True)

    global_batch = batch * n_chips
    rng = np.random.RandomState(0)
    data_sh = batch_sharding(mesh, DATA_AXIS)
    images = jax.device_put(
        rng.rand(global_batch, *img).astype(np.float32) * 2 - 1, data_sh)
    labels = jax.device_put(
        rng.randint(0, 5, size=(global_batch,)).astype(np.int32), data_sh)
    state = jax.device_put(state, replicated_sharding(mesh))
    key = jax.random.PRNGKey(1)

    # AOT: one compile, reused for both the FLOP count and every timed call.
    compiled = step.lower(state, images, labels, key).compile()
    _log("vision: compiled")
    flops = _compiled_flops(compiled)

    state, metrics = compiled(state, images, labels, key)  # warmup
    np.asarray(metrics["loss"])

    run_n = _chained_runner(step, compiled, state, (images, labels, key))

    dt, measured_steps = _time_steps(run_n)
    row = _row(global_batch, n_chips, dt, measured_steps, flops, peak,
               "images/sec/chip")
    row.update(_chain_ab_fields(run_n, dt, measured_steps))
    row["batch_per_chip"] = batch
    row["image"] = list(img)
    if vit_kw:  # non-default geometry: the A/B row must say what it measured
        row["model_shape"] = {"hidden": model.hidden,
                              "num_heads": model.num_heads}
    return row


def throwaway_image_package(tmp: str, img: tuple, quantize=None):
    """Frozen-random bf16 MobileNetV2 packaged into ``tmp`` and loaded back —
    the ONE serving fixture both ``bench_packaged_infer`` and
    ``tools/serving_curve.py`` measure, so their numbers describe the same
    artifact. Returns the loaded :class:`PackagedModel`."""
    import warnings

    from ddw_tpu.models.registry import build_model
    from ddw_tpu.serving.package import PackagedModel, save_packaged_model
    from ddw_tpu.utils.config import ModelCfg

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # frozen-random warning: speed only
        mcfg = ModelCfg(name="mobilenet_v2", num_classes=5, dropout=0.0,
                        freeze_base=True, allow_frozen_random=True,
                        dtype="bfloat16")
        model = build_model(mcfg)
        variables = model.init({"params": jax.random.PRNGKey(0)},
                               jnp.zeros((1, *img)), train=False)
        save_packaged_model(tmp, mcfg, [f"c{i}" for i in range(5)],
                            variables["params"],
                            variables.get("batch_stats"),
                            img_height=img[0], img_width=img[1],
                            quantize=quantize)
        return PackagedModel(tmp)


def bench_packaged_infer(*, batch: int, img: tuple, peak: float | None) -> dict:
    """Serving throughput through the packaged-model surface: the
    ``PackagedModel.predict_logits`` path the distributed scorer drives
    (fixed 128 sub-batch, per-chunk H2D/D2H — the honest end-to-end number a
    scorer worker sees, not a bare jitted forward). ``DDW_BENCH_INT8=1``
    serves the int8 weight-only artifact instead (transparent dequantize at
    load; reference role: the mlflow.pyfunc artifact each Spark executor
    loads, ``03_pyfunc_distributed_inference.py:157-184``)."""
    import tempfile

    from ddw_tpu.utils.config import env_flag as _flag

    quant = "int8" if _flag("DDW_BENCH_INT8") else None
    rng = np.random.RandomState(0)
    imgs = rng.rand(batch, *img).astype(np.float32) * 2 - 1

    with tempfile.TemporaryDirectory() as tmp:
        pm = throwaway_image_package(tmp, img, quantize=quant)
        pm.predict_logits(imgs)  # warmup: compile the 128-sub-batch apply
        _log("packaged_infer: compiled")

        def run_n(n):
            t0 = time.perf_counter()
            for _ in range(n):
                out = pm.predict_logits(imgs)
            # predict_logits fetches each chunk to host — completion forced
            float(out[0, 0])
            return time.perf_counter() - t0

        dt, measured = _time_steps(run_n)
    row = _row(batch, jax.device_count(), dt, measured, None, peak,
               "images/sec/chip")
    row.pop("chain", None)  # this row always host-loops (predict API path)
    row.update(batch_per_call=batch, image=list(img),
               quantization=quant or "none")
    return row


def bench_head_features(*, batch: int, feature_dim: int,
                        peak: float | None) -> dict:
    """The cached-feature transfer path (``ddw_tpu.train.transfer``): frozen
    backbone ran ONCE at prep, so the per-epoch train step is Dropout -> Dense
    fwd/bwd on pooled features. This row measures that step — the throughput a
    frozen-transfer user actually gets per epoch after the one-time featurize
    (compare against ``mobilenet_v2_frozen``, which re-runs the backbone
    forward every step the way the reference's Keras fit must)."""
    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS
    from ddw_tpu.train.step import (TrainState, batch_sharding, make_optimizer,
                                    make_train_step, replicated_sharding)
    from ddw_tpu.train.transfer import TransferHead
    from ddw_tpu.utils.config import TrainCfg

    devices = jax.devices()
    n_chips = len(devices)
    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)), devices=devices)

    model = TransferHead(num_classes=5, dropout=0.5)
    train_cfg = TrainCfg(batch_size=batch, optimizer="adam", learning_rate=1e-3)
    rng = np.random.RandomState(0)
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, feature_dim)), train=False)["params"]
    tx = make_optimizer(train_cfg)
    state = TrainState(params, {}, tx.init(params), jnp.zeros((), jnp.int32))
    step = make_train_step(model, tx, mesh, DATA_AXIS, donate=True)

    global_batch = batch * n_chips
    data_sh = batch_sharding(mesh, DATA_AXIS)
    feats = jax.device_put(
        rng.rand(global_batch, feature_dim).astype(np.float32), data_sh)
    labels = jax.device_put(
        rng.randint(0, 5, size=(global_batch,)).astype(np.int32), data_sh)
    state = jax.device_put(state, replicated_sharding(mesh))
    key = jax.random.PRNGKey(1)

    compiled = step.lower(state, feats, labels, key).compile()
    _log("head: compiled")
    flops = _compiled_flops(compiled)
    state, metrics = compiled(state, feats, labels, key)
    np.asarray(metrics["loss"])

    run_n = _chained_runner(step, compiled, state, (feats, labels, key))

    dt, measured_steps = _time_steps(run_n)
    row = _row(global_batch, n_chips, dt, measured_steps, flops, peak,
               "images/sec/chip")
    row.update(_chain_ab_fields(run_n, dt, measured_steps))
    row.update(batch_per_chip=batch, feature_dim=feature_dim)
    return row


def bench_e2e_loader(*, kind: str, batch: int, img: tuple,
                     peak: float | None) -> dict:
    """End-to-end loader-fed training: table on disk -> ShardedLoader -> chip.

    The synthetic rows measure the train step alone; this row measures the
    SYSTEM the reference's Petastorm converter feeds (``make_tf_dataset`` ->
    ``fit``, ``03_model_training_distributed.py:332-337``): records read from
    the sharded table store, batches assembled on host threads, transferred on
    the loader's prefetch thread (uint8 for ``raw_u8`` — 4x smaller H2D,
    dequantized on device), and consumed by the SAME jitted train step the
    synthetic row times. The e2e/synthetic ratio is the whole input-pipeline
    tax; BASELINE.md's host-pipeline section predicts ~1.0 for these
    materialized paths and ~1/65 for live JPEG decode on this 1-core host.

    ``kind='raw_u8'``: pre-decoded pixel table (``prep.materialize_decoded``)
    feeding the frozen-MobileNetV2 step — compare ``mobilenet_v2_frozen``.
    ``kind='feature_cache'``: pooled-feature table
    (``transfer.materialize_features``) feeding the head-only step — compare
    ``mobilenet_v2_frozen_feature_cache``.

    The table lives under a deterministic tempdir and is reused across
    runs (prep is one-time host work). Records cycle (infinite loader
    repeat), so host page cache serves the reads — stated in the row
    (``table_records``); this measures the assemble+transfer+step system, not
    cold disk.
    """
    import tempfile
    import warnings

    from ddw_tpu.data.loader import ShardedLoader
    from ddw_tpu.data.prep import (generate_synthetic_flowers,
                                   materialize_decoded, prepare_flowers)
    from ddw_tpu.data.store import TableStore
    from ddw_tpu.models.registry import build_model
    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS
    from ddw_tpu.train.step import (TrainState, batch_sharding, init_state,
                                    make_optimizer, make_train_step,
                                    replicated_sharding)
    from ddw_tpu.utils.config import ModelCfg, TrainCfg

    if kind not in ("raw_u8", "feature_cache"):
        raise ValueError(f"kind must be 'raw_u8' or 'feature_cache', got {kind!r}")

    devices = jax.devices()
    n_chips = len(devices)
    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)), devices=devices)
    global_batch = batch * n_chips
    h, w, _ = img

    per_class = 8 if SMOKE else 128
    root = os.path.join(tempfile.gettempdir(), f"ddw_e2e_{h}x{w}_{per_class}")
    store = TableStore(os.path.join(root, "store"))
    train_cfg = TrainCfg(batch_size=batch, optimizer="adam", learning_rate=1e-3)

    if not store.exists("silver_train"):
        generate_synthetic_flowers(os.path.join(root, "jpegs"),
                                   images_per_class=per_class, size=h)
        prepare_flowers(os.path.join(root, "jpegs"), store,
                        sample_fraction=1.0)
    silver = store.table("silver_train")

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # frozen-random warning: speed only
        mcfg = ModelCfg(name="mobilenet_v2", num_classes=5, dropout=0.5,
                        freeze_base=True, allow_frozen_random=True,
                        dtype="bfloat16")
        full = build_model(mcfg)
        full_state, full_tx = init_state(full, mcfg, train_cfg, img,
                                         jax.random.PRNGKey(0))

    if kind == "raw_u8":
        name = f"raw_{h}x{w}"
        if not store.exists(name):
            materialize_decoded(silver, store, name, h, w)
        table = store.table(name)
        model, state, tx = full, full_state, full_tx
    else:
        from ddw_tpu.train.transfer import TransferHead, materialize_features

        table = materialize_features(  # cached: fingerprint + freshness
            full, full_state.params, full_state.batch_stats, silver, store,
            f"feats_{h}x{w}", (h, w))
        model = TransferHead(num_classes=5, dropout=0.5)
        params = model.init(
            {"params": jax.random.PRNGKey(0)},
            jnp.zeros((1, table.meta["feature_dim"])), train=False)["params"]
        tx = make_optimizer(train_cfg)
        state = TrainState(params, {}, tx.init(params),
                           jnp.zeros((), jnp.int32))
    _log(f"e2e {kind}: setup done ({table.num_records} records)")

    data_sh = batch_sharding(mesh, DATA_AXIS)
    loader = ShardedLoader(table, batch_size=global_batch, image_size=(h, w),
                           num_epochs=None, shuffle=True, workers=4,
                           prefetch=4, prefetch_to=data_sh)
    it = iter(loader)
    step = make_train_step(model, tx, mesh, DATA_AXIS, donate=True)
    state = jax.device_put(state, replicated_sharding(mesh))
    key = jax.random.PRNGKey(1)

    imgs, lbls = next(it)
    compiled = step.lower(state, imgs, lbls, key).compile()
    _log(f"e2e {kind}: compiled")
    flops = _compiled_flops(compiled)
    state, metrics = compiled(state, imgs, lbls, key)  # warmup
    np.asarray(metrics["loss"])

    run_n = _chained_runner(step, compiled, state, (key,),
                            next_batch=lambda: next(it))

    dt, measured = _time_steps(run_n)
    row = _row(global_batch, n_chips, dt, measured, flops, peak,
               "images/sec/chip")
    # The loader feeds per-step from the host: this row is host-loop by
    # construction, whatever DDW_BENCH_CHAIN says.
    row["chain"] = "loop"
    row.update(batch_per_chip=batch, encoding=kind,
               table_records=table.num_records, pipeline="loader_prefetch")
    return row


def bench_lm(*, batch: int, seq: int, hidden: int, depth: int, heads: int,
             vocab: int, peak: float | None, num_experts: int = 0) -> dict:
    import optax

    from ddw_tpu.models.lm import TransformerLM
    from ddw_tpu.runtime.mesh import make_mesh, MeshSpec, DATA_AXIS
    from ddw_tpu.train.lm_step import init_lm_state, make_lm_train_step
    from ddw_tpu.train.step import replicated_sharding

    devices = jax.devices()
    n_chips = len(devices)
    mesh = make_mesh(MeshSpec(((DATA_AXIS, -1),)), devices=devices)

    # A/B knobs: DDW_BENCH_LM_REMAT=full|dots measures the remat FLOP/HBM
    # trade on the chip (default none — the headline row);
    # DDW_BENCH_LM_HEADS overrides the head count at IDENTICAL step FLOPs
    # (h512/H8 gives head_dim-64 attention dots at 50% MXU tile utilization;
    # H4 gives d128 full tiles — the ab_lm_tile arm).
    from ddw_tpu.utils.config import lm_heads_env

    heads = lm_heads_env(heads)
    model = TransformerLM(vocab_size=vocab, max_len=seq, hidden=hidden,
                          depth=depth, num_heads=heads, mlp_dim=hidden * 4,
                          dropout=0.0, dtype=jnp.bfloat16, seq_axis=None,
                          num_experts=num_experts,
                          remat=os.environ.get("DDW_BENCH_LM_REMAT", "none"))
    tx = optax.adam(3e-4)
    state = init_lm_state(model, tx, jax.random.PRNGKey(0), seq_len=8)
    step = make_lm_train_step(model, tx, mesh, DATA_AXIS, seq_axis=None,
                              donate=True)

    global_batch = batch * n_chips
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, vocab, size=(global_batch, seq + 1)).astype(np.int32)
    inputs = jax.device_put(tokens[:, :-1], step.batch_sharding)
    targets = jax.device_put(tokens[:, 1:], step.batch_sharding)
    state = jax.device_put(state, replicated_sharding(mesh))
    key = jax.random.PRNGKey(1)

    compiled = step.lower(state, inputs, targets, key).compile()
    _log("lm: compiled")
    flops = _compiled_flops(compiled)
    state, metrics = compiled(state, inputs, targets, key)
    np.asarray(metrics["loss"])

    run_n = _chained_runner(step, compiled, state, (inputs, targets, key))

    dt, measured_steps = _time_steps(run_n)
    row = _row(global_batch * seq, n_chips, dt, measured_steps, flops, peak,
               "tokens/sec/chip")
    row.update(_chain_ab_fields(run_n, dt, measured_steps))
    row.update(batch_per_chip=batch, seq_len=seq, hidden=hidden, depth=depth)
    if os.environ.get("DDW_BENCH_LM_HEADS"):
        row["num_heads"] = heads  # non-default geometry: say what ran
    if num_experts:
        row["num_experts"] = num_experts
    return row


def bench_host_pipeline(n_images: int, hw: int, device_ips: float | None) -> dict:
    """Host JPEG-decode feed rate: native C++ pool vs PIL, vs the device's
    consumption rate (SURVEY §7 hard-part 3 "measure").

    Source images are 2x the target (like the real flowers photos vs the 224
    model input), so the decoders' DCT-scaled decode paths (libjpeg
    scale_denom / PIL draft) are exercised the way production decode is."""
    import io

    src_hw = hw * 2
    from PIL import Image

    out: dict = {"n_images": n_images, "image": [hw, hw],
                 "source_image": [src_hw, src_hw]}

    rng = np.random.RandomState(0)
    contents = []
    for _ in range(n_images):
        arr = rng.randint(0, 255, size=(src_hw, src_hw, 3), dtype=np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, "JPEG", quality=85)
        contents.append(buf.getvalue())

    from ddw_tpu.native.decode import decode_batch_native

    t0 = time.perf_counter()
    _, ok = decode_batch_native(contents, hw, hw, threads=os.cpu_count() or 4)
    dt_native = time.perf_counter() - t0
    out["native_images_per_sec"] = round(n_images / dt_native, 1)
    out["native_ok_fraction"] = round(float(ok.mean()), 3)

    # Same work as the native path (decode + resize + scale-to-[-1,1]) so the
    # comparison is fair; single-threaded PIL, the per-image re-decode path.
    from ddw_tpu.data.loader import _preprocess_image_pil

    t0 = time.perf_counter()
    for c in contents:
        _preprocess_image_pil(c, hw, hw)
    out["pil_images_per_sec"] = round(n_images / (time.perf_counter() - t0), 1)

    # Materialized raw_u8 path (prep.materialize_decoded): memcpy + scale,
    # through the shared scheme helpers.
    from ddw_tpu.data.loader import dequantize_raw_u8, raw_u8_view

    raws = [np.clip(np.round((_preprocess_image_pil(c, hw, hw) + 1) * 127.5),
                    0, 255).astype(np.uint8).tobytes() for c in contents[:64]]
    batch = np.empty((len(raws), hw, hw, 3), np.float32)
    reps = max(1, n_images // len(raws))
    t0 = time.perf_counter()
    for _ in range(reps):
        for j, r in enumerate(raws):
            batch[j] = raw_u8_view(r, hw, hw)
        dequantize_raw_u8(batch)
    out["raw_u8_images_per_sec"] = round(
        reps * len(raws) / (time.perf_counter() - t0), 1)

    if device_ips:
        # >1: one host's decode pool alone outruns the chip; <1: the chip
        # starves unless decode scales out (more threads/hosts) or data is
        # pre-decoded into the table store (the default training path).
        out["native_feed_headroom_vs_device"] = round(
            out["native_images_per_sec"] / device_ips, 4)
    return out


def _device_problem() -> str | None:
    """Full shapes measure a TPU and nothing else: a one-line refusal naming
    the platform found, or None. The CPU smoke (``DDW_BENCH_SMOKE``) runs
    anywhere — it checks structure, and its numbers are not device metrics."""
    dev = jax.devices()[0]
    if SMOKE or dev.platform == "tpu":
        return None
    return (f"JAX platform is {dev.platform!r} ({dev.device_kind}), not 'tpu'; "
            f"refusing to record its timings as chip results "
            f"(DDW_BENCH_SMOKE=1 runs the CPU structure check)")


# Static matrix names: DDW_BENCH_ONLY validates against these BEFORE any
# device init, so a typo fails at once.
_CONFIG_NAMES = ("mobilenet_v2_frozen", "mobilenet_v2_frozen_feature_cache",
                 "mobilenet_v2_unfrozen", "resnet50", "vit", "lm_flash",
                 "lm_moe", "packaged_infer", "e2e_raw_u8", "e2e_feature_cache")


def _json_error_exit(message: str, code: int) -> None:
    """The one-JSON-line failure contract every exit path honors."""
    print(json.dumps({
        "metric": "mobilenet_v2_frozen_train_images_per_sec_per_chip",
        "value": None,
        "unit": "images/sec/chip",
        "vs_baseline": None,
        "error": message,
    }))
    sys.stdout.flush()
    sys.exit(code)


def main():
    only = [s.strip() for s in os.environ.get("DDW_BENCH_ONLY", "").split(",")
            if s.strip()]
    unknown = sorted(set(only) - set(_CONFIG_NAMES))
    if unknown:
        # a typo'd config name must leave a parseable record, not a bare
        # traceback — and must fail BEFORE device init
        _json_error_exit(f"DDW_BENCH_ONLY names unknown configs {unknown}; "
                         f"have {sorted(_CONFIG_NAMES)}", 2)

    problem = _device_problem()
    if problem:
        _json_error_exit(problem, 1)
    enable_compile_cache()

    kind, peak = _device_peak_tflops()
    n_chips = len(jax.devices())

    if SMOKE:
        img, batch = (64, 64, 3), 8
        lm_kw = dict(batch=8, seq=128, hidden=64, depth=2, heads=4, vocab=256,
                     peak=peak)
        host_n, host_hw = 16, 64
    else:
        img, batch = (224, 224, 3), 256
        lm_kw = dict(batch=8, seq=2048, hidden=512, depth=6, heads=8,
                     vocab=8192, peak=peak)
        host_n, host_hw = 512, 224

    matrix = {
        "mobilenet_v2_frozen": lambda: bench_vision(
            "mobilenet_v2", freeze_base=True, batch=batch, img=img, peak=peak),
        "mobilenet_v2_frozen_feature_cache": lambda: bench_head_features(
            batch=batch, feature_dim=1280, peak=peak),
        "mobilenet_v2_unfrozen": lambda: bench_vision(
            "mobilenet_v2", freeze_base=False, batch=batch, img=img, peak=peak),
        "resnet50": lambda: bench_vision(
            "resnet50", freeze_base=False, batch=batch, img=img, peak=peak),
        "vit": lambda: bench_vision(
            "vit", freeze_base=False, batch=batch, img=img, peak=peak),
        "lm_flash": lambda: bench_lm(**lm_kw),
        "lm_moe": lambda: bench_lm(**lm_kw, num_experts=8),
        "packaged_infer": lambda: bench_packaged_infer(
            batch=batch, img=img, peak=peak),
        "e2e_raw_u8": lambda: bench_e2e_loader(
            kind="raw_u8", batch=batch, img=img, peak=peak),
        "e2e_feature_cache": lambda: bench_e2e_loader(
            kind="feature_cache", batch=batch, img=img, peak=peak),
    }
    if set(matrix) != set(_CONFIG_NAMES):  # not assert: -O strips, and the
        _json_error_exit(                  # contract wants JSON, not a trace
            f"bench.py bug: matrix {sorted(matrix)} drifted from "
            f"_CONFIG_NAMES {sorted(_CONFIG_NAMES)} — update both", 2)
    if only:  # names validated against _CONFIG_NAMES at the top of main
        matrix = {k: v for k, v in matrix.items() if k in only}

    configs: dict = {}
    failed: list[str] = []
    for name, fn in matrix.items():
        _log(f"{name}: compile + measure")
        try:
            row = fn()
        except Exception as e:
            # one broken config must not hide the others: its row says what
            # happened, the matrix is still printed, and the run exits 1
            configs[name] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(name)
            _log(f"{name}: ERROR {e}")
            continue
        anchor = CHIP_ANCHORS.get(name)
        rate = row.get("rate_per_chip")
        if anchor and rate and not SMOKE:  # full shapes run on a TPU only
            row["vs_anchor"] = round(rate / anchor[0], 3)
            row["anchor_round"] = anchor[1]
        ceiling = MFU_CEILINGS.get(name)
        # v5e-only like the ceilings themselves (mxu_roofline derives
        # them from v5e peak/bandwidth + these exact headline shapes);
        # on another TPU generation frac_of_ceiling would be fiction.
        if (ceiling and not SMOKE and row.get("mfu")
                and ("v5e" in kind.lower() or "v5 lite" in kind.lower())):
            row["mfu_ceiling"] = ceiling
            row["frac_of_ceiling"] = round(row["mfu"] / ceiling, 4)
        configs[name] = row
        _log(f"{name}: done ({row.get('rate_per_chip')} {row.get('unit')})")

    _log("host pipeline")
    try:  # a host-side failure must not discard the measured device matrix
        host = bench_host_pipeline(
            host_n, host_hw,
            configs.get("mobilenet_v2_frozen", {}).get("rate_per_chip"))
    except Exception as e:
        host = {"error": f"{type(e).__name__}: {e}"}
        failed.append("host_pipeline")
        _log(f"host pipeline: ERROR {e}")

    ips = configs.get("mobilenet_v2_frozen", {}).get("rate_per_chip")
    dev = jax.devices()[0]
    payload = {
        "metric": "mobilenet_v2_frozen_train_images_per_sec_per_chip",
        "value": ips,
        "unit": "images/sec/chip",
        "vs_baseline": round(ips / BASELINE_IPS, 3) if ips else None,
        "device": {"platform": dev.platform, "kind": kind, "n": n_chips,
                   "peak_bf16_tflops": peak},
        "configs": configs,
        "host_pipeline": host,
    }
    if failed:
        payload["error"] = f"failed: {', '.join(failed)}"
    print(json.dumps(payload))
    sys.stdout.flush()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
