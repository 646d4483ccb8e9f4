"""Attention tiers and flash-kernel blocks, timed on the chip — one JSON line an arm.

The table this prints is what ``ops/flash_attention.py`` cites for its dispatch
constant (``_FLASH_MIN_SEQ``) and its block caps, and what ``PERF.md`` section 5
quotes. Presets (all bfloat16, D = 64 unless ``--dim``):

- ``cells``: the two attention shapes the benchmark's cells run —
  ``[8,16,1024,64]`` causal (GPT-2 medium, batch 8) and ``[128,12,196,64]`` not
  causal (ViT-B/16, batch 128) — forward + backward by tier (``impl=`` of
  ``flash_mha``): ``xla``, ``xla_ckpt``, ``pallas`` at the blocks the code picks.
- ``ladder``: S in 256..4096 at B*H*S = 131072 tokens, causal and not, the same
  three tiers: where the kernels start to win.
- ``short``: the ladder under ``_FLASH_MIN_SEQ`` — S in 32..512 at B*S = 8192
  tokens x 16 heads, causal and not, and ViT's shape in both operand layouts —
  with a fourth arm, ``pallas_short`` (the one-block kernels), beside the
  streaming kernels; then the one-block forward and backward alone at ViT's
  shape over the batch rows a grid step takes.
- ``blocks``: at one shape (``--shape B,H,S,D`` or ``B,H,S,D|Dv`` for v heads
  of another width; default the LM cell's), the forward kernel and the
  one-pass backward alone over (block_q, block_k, sub_k) and at the chosen
  blocks (``--grid chosen``: those alone). ``--dq-home vmem,hbm`` times the
  backward with dQ's sum in each home beside the code's own choice.
  ``--beside FILE`` loads another tree's ``flash_attention.py`` and times its
  backward at the chosen blocks too (a tree from before PR 45 has ``dq`` and
  ``dkv`` where this one has ``bwd``); this tree's rows there then carry
  ``max_abs_diff``, the largest distance of its dq, dk, dv from that tree's.

An arm that fails to compile or to fit prints ``"ms": null`` and the error.
``--profile DIR`` also traces one forward + backward of the first shape's
``pallas`` arm and prints the device operations by name, which is how the
kernels' names in a profile were found (``benchmark/metrics/attention_kernel_ms.py``).

Run on the TPU:  python tools/fa2_sweep.py --preset cells
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import importlib
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

# ddw_tpu.ops re-exports a flash_attention FUNCTION that shadows the module
fa = importlib.import_module("ddw_tpu.ops.flash_attention")

# shape [B,H,S,D], causal, sequence-major operands (the LM) or not (ViT)
CELL_SHAPES = (((8, 16, 1024, 64), True, True),
               ((128, 12, 196, 64), False, False))
LADDER_SEQS = (256, 512, 1024, 2048, 4096)
LADDER_TOKENS = 131072
TIERS = ("xla", "xla_ckpt", "pallas")
SHORT_SEQS = (32, 64, 128, 196, 256, 384, 512)
SHORT_TIERS = TIERS + ("pallas_short",)
SHORT_IMAGES = (1, 2, 4, 8, 16)
BLOCK_GRID = tuple((bq, bk, sub) for bq in (256, 512, 1024)
                   for bk in (512, 1024) for sub in (128, 256, 512)
                   if sub <= bk)


def time_ms(fn, *args, min_s: float = 0.25, repeats: int = 3) -> float:
    """Median milliseconds a call: ``n`` calls enqueued back to back, ended by
    ``block_until_ready``, n grown until a batch takes ``min_s``."""
    jax.block_until_ready(fn(*args))            # compile + warm up

    def batch(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    n = 4
    while batch(n) < min_s and n < 4096:
        n *= 2
    return statistics.median(batch(n) for _ in range(repeats)) / n * 1e3


def qkv(shape, dtype=jnp.bfloat16, n=3):
    rng = np.random.RandomState(0)
    return [jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.5, dtype)
            for _ in range(n)]


def tier_fn(impl: str, causal: bool, seq_major: bool = False):
    """Forward + backward of one attention call, the gradients folded into the
    returned scalar (returning the loss alone would let XLA drop the backward
    pass). ``seq_major``: operands ``[B,S,H,D]`` through the entry the LM
    calls, else ``[B,H,S,D]`` through ``flash_mha`` (ViT, the ring)."""
    attend = fa.flash_mha_seq_major if seq_major else fa.flash_mha

    @jax.jit
    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            out = attend(q, k, v, causal=causal, impl=impl)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l + sum(jnp.sum(g.astype(jnp.float32)) for g in grads)

    return fwd_bwd


def matmul_flops(shape, causal: bool, units: float) -> float:
    """``units`` S x S x D matmuls of 2 FLOPs a multiply-add (forward 2,
    backward 5 without recomputation), halved where causal."""
    b, h, s, d = shape
    return units * 2.0 * b * h * s * s * d * (0.5 if causal else 1.0)


def chosen_blocks(s: int):
    """(block_q, block_k, sub_k) the code picks for self-attention of length
    ``s``, after ``flash_mha``'s padding to a block multiple."""
    bq = fa._pick_block(s, None, fa._BLOCK_Q_MAX)
    bk = fa._pick_block(s, None, fa._BLOCK_K_MAX)
    return list(fa._resolve_blocks(-(-s // bq) * bq, -(-s // bk) * bk,
                                   bq, bk, None))


def chosen_images(shape) -> int:
    """Batch rows a grid step the one-block kernels pick for ``[B,H,S,D]``."""
    return fa._pick_images(shape[0], fa._short_pad(shape[2], 128), 128, 2)


def emit(row: dict, device: str):
    print(json.dumps({"tool": "fa2_sweep", "device": device, **row}),
          flush=True)


def run_tiers(preset, shapes, device, tiers=TIERS):
    """``shapes``: (B,H,S,D), causal, whether the operands are sequence-major."""
    for shape, causal, seq_major in shapes:
        b, h, s, d = shape
        q, k, v = qkv((b, s, h, d) if seq_major else shape)
        for impl in tiers:
            row = {"preset": preset, "shape": list(shape), "dtype": "bfloat16",
                   "causal": causal, "arm": impl, "pass": "fwd+bwd",
                   "layout": "bshd" if seq_major else "bhsd"}
            if impl == "pallas":
                row["blocks"] = chosen_blocks(shape[2])
            elif impl == "pallas_short":
                row["images"] = chosen_images(shape)
            try:
                fn = tier_fn(impl, causal, seq_major)
                ms = time_ms(fn, q, k, v)
                # loss + summed gradients: the tiers must agree on it
                row.update(ms=round(ms, 4), value=float(fn(q, k, v)),
                           tflops=round(
                               matmul_flops(shape, causal, 7) / ms / 1e9, 2))
            except Exception as e:      # an arm that cannot run is a row too
                row.update(ms=None, error=f"{type(e).__name__}: {e}"[:300])
            emit(row, device)


def load_beside(path: str):
    """Another tree's ``flash_attention.py`` as a module of its own (its
    imports of ``ddw_tpu`` resolve to this tree's, which it shares)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("fa_beside", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def backward_arms(module, blocks, homes=(None,)):
    """name -> (function of (q, k, v, g, lse, dvec), matmul units, which of
    dq, dk, dv it returns): the one-pass backward where ``module`` has it (once
    a home of dQ's sum, None the code's choice), else its dQ and dK/dV kernels."""
    if not hasattr(module, "_flash_bwd"):
        return {"dq": (jax.jit(lambda *a: module._flash_dq(*a, *blocks)),
                       3, ("dq",)),
                "dkv": (jax.jit(lambda *a: module._flash_dkv(*a, *blocks)),
                        4, ("dk", "dv"))}

    def forced(home):
        def fn(*a):
            chooser = module._dq_home
            if home is not None:
                module._dq_home = lambda *_: home
            try:        # the jitted entry would remember its first choice
                return module._flash_bwd.__wrapped__(*a, *blocks)
            finally:
                module._dq_home = chooser
        return jax.jit(fn)

    return {"bwd" if home is None else f"bwd_{home}":
            (forced(home), 5, ("dq", "dk", "dv")) for home in homes}


def run_blocks(shape, dv, causal, device, grid=BLOCK_GRID, homes=(),
               beside=None):
    b, h, s, d = shape
    q, k = qkv((b, s, h, d), n=2)                # as the kernels take them
    v, g = qkv((b, s, h, dv), n=2)
    scale, interpret = fa._resolve_defaults(None, None, d)
    out, lse = jax.jit(lambda q, k, v: fa._flash_forward(
        q, k, v, causal, 0, 0, scale, None, None, interpret))(q, k, v)
    dvec = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1).transpose(0, 2, 1)
    bwd_args = (q, k, v, g, lse, dvec)
    chosen = fa._resolve_blocks(s, s, None, None, None)
    for bq, bk, sub in dict.fromkeys((chosen,) + tuple(grid)):
        if bq > s or bk > s or bk % sub:
            continue
        blocks = (causal, 0, 0, scale, bq, bk, interpret, None, sub)
        is_chosen = (bq, bk, sub) == chosen
        arms = {"fwd": (jax.jit(lambda q, k, v: fa._flash_forward(
            q, k, v, *blocks)), 2, ())}
        if beside is not None and is_chosen:    # before this tree's rows,
                                                # which compare with them
            arms.update({f"beside_{name}": arm for name, arm in
                         backward_arms(beside, blocks).items()})
        arms.update(backward_arms(
            fa, blocks, (None,) + (tuple(homes) if is_chosen else ())))
        theirs = {}         # the other tree's dq, dk, dv at these blocks
        for name, (fn, units, gives) in arms.items():
            row = {"preset": "blocks", "shape": list(shape), "v_dim": dv,
                   "dtype": "bfloat16", "causal": causal, "arm": name,
                   "blocks": [bq, bk, sub], "chosen": is_chosen}
            args = bwd_args if gives else (q, k, v)
            try:
                ms = time_ms(fn, *args, min_s=0.1)
                row.update(ms=round(ms, 4), tflops=round(matmul_flops(
                    (b, h, s, (d + dv) / 2), causal, units) / ms / 1e9, 2))
                if gives and beside is not None and is_chosen:
                    got = fn(*args)
                    got = dict(zip(gives, got if isinstance(got, tuple)
                                   else (got,)))
                    if name.startswith("beside_"):
                        theirs.update(got)
                    else:
                        row["max_abs_diff"] = {
                            what: float(jnp.max(jnp.abs(
                                val.astype(jnp.float32)
                                - theirs[what].astype(jnp.float32))))
                            for what, val in got.items() if what in theirs}
            except Exception as e:
                row.update(ms=None, error=f"{type(e).__name__}: {e}"[:300])
            emit(row, device)


def run_short_images(shape, causal, device, grid=SHORT_IMAGES):
    """The one-block forward and backward alone, by batch rows a grid step."""
    b, h, s, d = shape
    q, k, v, g = qkv((b, s, h, d), n=4)
    scale, interpret = fa._resolve_defaults(None, None, d)
    out, lse = fa._short_forward(q, k, v, causal, scale, interpret)
    chosen = chosen_images(shape)
    for images in grid:
        if b % images:
            continue
        arms = {
            "short_fwd": (jax.jit(lambda q, k, v: fa._short_forward(
                q, k, v, causal, scale, interpret, images)), (q, k, v), 2),
            "short_bwd": (jax.jit(lambda *a: fa._short_backward(
                *a, causal, scale, interpret, images)),
                (q, k, v, out, lse, g, jnp.zeros_like(lse)), 5),
        }
        for name, (fn, args, units) in arms.items():
            row = {"preset": "short", "shape": list(shape),
                   "dtype": "bfloat16", "causal": causal, "arm": name,
                   "images": images, "chosen": images == chosen}
            try:
                ms = time_ms(fn, *args, min_s=0.1)
                row.update(ms=round(ms, 4), tflops=round(
                    matmul_flops(shape, causal, units) / ms / 1e9, 2))
            except Exception as e:
                row.update(ms=None, error=f"{type(e).__name__}: {e}"[:300])
            emit(row, device)


def profile_names(shape, causal, trace_dir, device):
    """One traced forward + backward of the kernels; the device operations'
    names and summed times, longest first."""
    from benchmark.harness import trace_reduce

    b, h, s, d = shape
    fn = tier_fn("pallas", causal, seq_major=True)
    args = qkv((b, s, h, d))
    jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(trace_dir)
    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    red = trace_reduce.reduce(
        trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir)), top=24)
    emit({"preset": "profile", "shape": list(shape), "causal": causal,
          "calls": 3, "top_ops_ns": red["top_ops"],
          "top_families_ns": red["top_families"]}, device)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preset", default="cells",
                    help="comma list of cells, ladder, short, blocks")
    ap.add_argument("--shape", default="8,16,1024,64",
                    help="B,H,S,D (or B,H,S,D|Dv) of the blocks preset")
    ap.add_argument("--grid", default="all", choices=("all", "chosen"),
                    help="blocks preset: every block triple, or the chosen")
    ap.add_argument("--dq-home", default="",
                    help="blocks preset: comma list of vmem, hbm to force")
    ap.add_argument("--beside", default=None, metavar="FILE",
                    help="blocks preset: another tree's flash_attention.py")
    ap.add_argument("--dim", type=int, default=64,
                    help="head dimension of the ladder and short presets")
    ap.add_argument("--not-causal", action="store_true",
                    help="the blocks preset without the causal mask")
    ap.add_argument("--tiers", default=None,
                    help="comma list of arms (default: the preset's own)")
    ap.add_argument("--profile", default=None, metavar="DIR")
    args = ap.parse_args()
    from ddw_tpu.utils.config import require_tpu_or_exit
    device = require_tpu_or_exit("sweep")
    tiers = tuple(args.tiers.split(",")) if args.tiers else None
    dims, _, v_dim = args.shape.partition("|")
    shape = tuple(int(x) for x in dims.split(","))
    for preset in args.preset.split(","):
        if preset == "cells":
            run_tiers(preset, CELL_SHAPES, device, tiers or TIERS)
        elif preset == "ladder":
            run_tiers(preset, [((LADDER_TOKENS // s // 16, 16, s, args.dim),
                                 causal, True) for s in LADDER_SEQS
                               for causal in (True, False)], device,
                      tiers or TIERS)
        elif preset == "short":
            vit = CELL_SHAPES[1][0]
            run_tiers(preset, [(vit, False, False), (vit, False, True)]
                      + [((8192 // s, 16, s, args.dim), causal, True)
                         for s in SHORT_SEQS for causal in (False, True)],
                      device, tiers or SHORT_TIERS)
            run_short_images(vit, False, device)
        elif preset == "blocks":
            run_blocks(shape, int(v_dim or shape[3]), not args.not_causal,
                       device, BLOCK_GRID if args.grid == "all" else (),
                       tuple(filter(None, args.dq_home.split(","))),
                       args.beside and load_beside(args.beside))
        else:
            ap.error(f"unknown preset {preset!r}")
    if args.profile:
        profile_names(CELL_SHAPES[0][0], True, args.profile, device)


if __name__ == "__main__":
    main()
