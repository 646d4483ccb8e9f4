"""MXU tile-quantized roofline for the transformer rows — no device needed.

``tools/roofline.py``'s transformer floor assumes every matmul runs at the
MXU's peak rate; that is wrong for the bench shapes. The v5e MXU is a
128x128 systolic array: a ``dot_general`` only streams at peak when its
contracting (K) and rhs-output (N) dims fill 128-wide tiles (and the lhs
rows fill the 8-deep sublane quantum). The bench ViT is h192/heads4 —
every projection contracts K=192 (1.5 tiles -> 75%), and its attention dots
have head_dim 48 (K or N = 48/128 = 37.5%). This tool computes the honest
ceiling (VERDICT r4 item 2: "a corrected roofline proving a lower ceiling"):

1. lower the EXACT bench train step at headline shapes (CPU, abstract — the
   same lowering ``tools/attn_dispatch_evidence.py`` uses);
2. parse every ``stablehlo.dot_general``'s shapes + dimension numbers;
3. per dot: actual MACs = B*M*N*K vs tile-padded MACs =
   B*ceil8(M)*ceil128(N)*ceil128(K); module MXU utilization = sum(actual) /
   sum(padded);
4. corrected floor = roofline.transformer_floor with its MXU term divided
   by that utilization (HBM + optimizer terms unchanged).

The quantization model is an approximation of the v5e (padding quanta
M->8, K->128, N->128; real tiling also depends on dtype packing and layout
choice — XLA may transpose to put the better dim on the lanes), so treat
the output as a *ceiling correction*, not a prediction. It never loosens
the physics: padded >= actual always.

Usage: ``python tools/mxu_roofline.py [--configs vit,lm_flash]``.
Prints ONE JSON line; table on stderr.  CI smoke: ``DDW_BENCH_SMOKE=1``.
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import argparse
import json
import math
import re
import subprocess

# one worker subprocess per config keeps the big lowerings isolated (and the
# CPU platform forced) exactly like attn_dispatch_evidence; the lowering
# itself is SHARED with that tool so the two can never analyze different
# programs, and SMOKE uses its exact truthiness rules
from attn_dispatch_evidence import (  # noqa: E402
    CONFIGS,
    SMOKE,
    lower_bench_step,
)

# batching_dims is omitted from the text when empty (plain projections/MLP)
_DOT_RE = re.compile(
    r"stablehlo\.dot_general\s+[^:]*?"
    r"(?:batching_dims = \[([\d, ]*)\] x \[([\d, ]*)\], )?"
    r"contracting_dims = \[([\d, ]*)\] x \[([\d, ]*)\].*?"
    r": \(tensor<([\dx]+)x[a-z0-9]+>, tensor<([\dx]+)x[a-z0-9]+>\)")


def _dims(s: str) -> list:
    return [int(x) for x in s.split(",") if x.strip()]


def _shape(s: str) -> list:
    return [int(x) for x in s.split("x")]


def _ceil(n: int, q: int) -> int:
    return q * math.ceil(n / q)


def dot_rows(stablehlo_text: str) -> list:
    """Every dot_general as {B, M, N, K, macs, padded_macs, util}."""
    rows = []
    for m in _DOT_RE.finditer(stablehlo_text):
        lb = _dims(m.group(1)) if m.group(1) is not None else []
        rb = _dims(m.group(2)) if m.group(2) is not None else []
        lc, rc = _dims(m.group(3)), _dims(m.group(4))
        lshape, rshape = _shape(m.group(5)), _shape(m.group(6))
        B = math.prod(lshape[i] for i in lb) if lb else 1
        K = math.prod(lshape[i] for i in lc) if lc else 1
        M = math.prod(d for i, d in enumerate(lshape)
                      if i not in lb and i not in lc)
        N = math.prod(d for i, d in enumerate(rshape)
                      if i not in rb and i not in rc)
        macs = B * M * N * K
        padded = B * _ceil(M, 8) * _ceil(N, 128) * _ceil(K, 128)
        rows.append({"B": B, "M": M, "N": N, "K": K, "macs": macs,
                     "padded_macs": padded, "util": macs / padded})
    return rows


def analyze(text: str, top: int = 6) -> dict:
    rows = dot_rows(text)
    macs = sum(r["macs"] for r in rows)
    padded = sum(r["padded_macs"] for r in rows)
    # aggregate identical shapes (a 6-deep transformer repeats everything)
    agg: dict = {}
    for r in rows:
        key = (r["B"], r["M"], r["N"], r["K"])
        a = agg.setdefault(key, {"count": 0, "macs": 0, "padded": 0})
        a["count"] += 1
        a["macs"] += r["macs"]
        a["padded"] += r["padded_macs"]
    worst = sorted(agg.items(), key=lambda kv: -kv[1]["padded"])[:top]
    return {
        "n_dots": len(rows),
        "macs": macs,
        "padded_macs": padded,
        "mxu_util": macs / padded if padded else 1.0,
        "top_shapes": [
            {"BMNK": list(k), "count": v["count"],
             "gmacs": round(v["macs"] / 1e9, 2),
             "padded_gmacs": round(v["padded"] / 1e9, 2),
             "util": round(v["macs"] / v["padded"], 3),
             "share_of_padded": round(v["padded"] / padded, 3)}
            for k, v in worst],
    }


def corrected_floor(config: str, util: float, dims: dict) -> dict:
    """roofline.transformer_floor with the MXU term divided by util.

    ``dims`` comes from ``lower_bench_step`` — the real model's geometry —
    so the naive baseline and the lowered module can never desync."""
    from roofline import HBM_GBPS, PEAK_TFLOPS, transformer_floor

    naive = transformer_floor(config, batch=dims["batch"],
                              seq=dims["seqlen"], hidden=dims["hidden"],
                              depth=dims["depth"], mlp_dim=dims["mlp_dim"],
                              vocab=dims["vocab"])
    t_mxu = naive["flops"] / (PEAK_TFLOPS * 1e12) / util
    t_hbm = naive["bytes"] / (HBM_GBPS * 1e9)
    t_opt = naive["floor_ms"] / 1e3 - max(
        naive["flops"] / (PEAK_TFLOPS * 1e12), t_hbm)
    floor = max(t_mxu, t_hbm) + t_opt
    return {"naive_floor_ms": round(naive["floor_ms"], 2),
            "corrected_floor_ms": round(floor * 1e3, 2),
            "naive_mfu_ceiling": round(naive["mfu_ceiling"], 3),
            "corrected_mfu_ceiling": round(
                naive["flops"] / floor / (PEAK_TFLOPS * 1e12), 3)}


def worker(config: str) -> dict:
    """Lower the bench step (the SAME lowering attn_dispatch_evidence uses,
    default dispatch arm) and attach quantization analysis + corrected
    floor."""
    text, dims = lower_bench_step(config)
    out = {"config": config, **analyze(text)}
    if not SMOKE:
        out.update(corrected_floor(config, out["mxu_util"], dims))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", default="", help=argparse.SUPPRESS)
    ap.add_argument("--configs", default="vit,lm_flash")
    args = ap.parse_args()

    if args.worker:
        print(json.dumps(worker(args.worker)))
        return

    out: dict = {"configs": {}}
    for config in args.configs.split(","):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=1",
                   PYTHONPATH=os.path.dirname(
                       os.path.dirname(os.path.abspath(__file__))))
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker", config],
            capture_output=True, text=True, env=env, timeout=1800)
        if r.returncode != 0:
            out["configs"][config] = {"error": r.stderr[-800:]}
            continue
        d = json.loads(r.stdout.strip().splitlines()[-1])
        out["configs"][config] = d
        print(f"[{config:<8}] mxu_util={d['mxu_util']:.3f} over "
              f"{d['n_dots']} dots", file=sys.stderr)
        for s in d["top_shapes"]:
            print(f"   BMNK={str(s['BMNK']):<26} x{s['count']:<3} "
                  f"util={s['util']:<6} share={s['share_of_padded']}",
                  file=sys.stderr)
        if "corrected_floor_ms" in d:
            print(f"   floor: naive {d['naive_floor_ms']} ms "
                  f"(MFU ceil {d['naive_mfu_ceiling']:.0%}) -> corrected "
                  f"{d['corrected_floor_ms']} ms "
                  f"({d['corrected_mfu_ceiling']:.0%})",
                  file=sys.stderr, flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
