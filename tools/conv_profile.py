"""Per-conv attribution + roofline analysis for the conv train steps.

A conv train step's MFU says the chip idles but not WHERE. This tool answers
that without TensorBoard: it enumerates every conv layer of a model (shape,
stride, groups), microbenchmarks each unique conv fwd+bwd in isolation with
the differential forced-fetch timing ``tools/chip_kernels.py`` holds, and
compares the measured time against BOTH hardware ceilings:

- compute bound: ``flops / peak_bf16_flops``
- memory bound:  ``bytes_moved / hbm_bandwidth``

A layer running near ``max(compute_bound, memory_bound)`` is at its roofline —
the remaining MFU gap is physics (e.g. depthwise convs move ~1 byte per flop
and can never reach MXU rates). A layer far above both bounds is fixable
(layout, padding, fusion, accumulation dtype).

The per-layer sum vs the measured whole-step time also bounds what XLA's
cross-layer fusion is worth.

Run on the TPU:  PYTHONPATH=. python tools/conv_profile.py [model ...]
(models: mobilenet_v2 resnet50; add ``--batch N`` ``--img N``)
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

# v5e peaks, assumed for ANY device (ROADMAP S1: one table keyed by
# device_kind, unknown device = error)
PEAK_TFLOPS = 197.0   # v5e bf16
HBM_GBPS = 819.0      # v5e HBM bandwidth


@dataclass(frozen=True)
class ConvSpec:
    name: str
    in_hw: int
    cin: int
    cout: int
    k: int
    stride: int
    groups: int = 1

    @property
    def out_hw(self) -> int:
        return -(-self.in_hw // self.stride)  # SAME padding

    def fwd_flops(self, batch: int) -> float:
        """Forward MACs * 2."""
        return (2 * batch * self.out_hw ** 2 * self.k ** 2
                * (self.cin // self.groups) * self.cout)

    def flops(self, batch: int) -> float:
        """fwd + bwd; bwd ~2x fwd (dx + dw) => 3x fwd total."""
        return 3.0 * self.fwd_flops(batch)

    def bytes_fwd(self, batch: int) -> float:
        """Minimal fwd HBM traffic in bf16: read in + w, write out."""
        act_in = batch * self.in_hw ** 2 * self.cin * 2
        act_out = batch * self.out_hw ** 2 * self.cout * 2
        w = self.k ** 2 * (self.cin // self.groups) * self.cout * 2
        return act_in + act_out + w

    def bytes_moved(self, batch: int) -> float:
        """Minimal HBM traffic for fwd+bwd in bf16.

        fwd: read in + w, write out.
        bwd: read dout + w + saved-in, write din + dw.
        => act_in 3x (2 reads + din write), act_out 2x (out write + dout
        read), w 3x (2 reads + dw write)."""
        act_in = batch * self.in_hw ** 2 * self.cin * 2
        act_out = batch * self.out_hw ** 2 * self.cout * 2
        w = self.k ** 2 * (self.cin // self.groups) * self.cout * 2
        return 3 * act_in + 2 * act_out + 3 * w


def mobilenet_v2_convs(img: int, width: float = 1.0) -> list[ConvSpec]:
    from ddw_tpu.models.mobilenet_v2 import _INVERTED_RESIDUAL_CFG, _make_divisible

    specs = []
    hw = -(-img // 2)
    cin = _make_divisible(32 * width)
    specs.append(ConvSpec("stem", img, 3, cin, 3, 2))
    for bi, (t, c, n, s) in enumerate(_INVERTED_RESIDUAL_CFG):
        cout = _make_divisible(c * width)
        for i in range(n):
            stride = s if i == 0 else 1
            hidden = cin * t
            if t != 1:
                specs.append(ConvSpec(f"b{bi}.{i}.expand", hw, cin, hidden, 1, 1))
            specs.append(ConvSpec(f"b{bi}.{i}.dw", hw, hidden, hidden, 3,
                                  stride, groups=hidden))
            hw = -(-hw // stride)
            specs.append(ConvSpec(f"b{bi}.{i}.proj", hw, hidden, cout, 1, 1))
            cin = cout
    specs.append(ConvSpec("top", hw, cin, _make_divisible(1280 * max(1.0, width)),
                          1, 1))
    return specs


def resnet50_convs(img: int) -> list[ConvSpec]:
    specs = [ConvSpec("stem", img, 3, 64, 7, 2)]
    hw = -(-img // 4)  # stem stride 2 + maxpool stride 2
    cin = 64
    for stage, (blocks, cmid) in enumerate(zip((3, 4, 6, 3), (64, 128, 256, 512))):
        cout = cmid * 4
        for i in range(blocks):
            stride = 2 if (i == 0 and stage > 0) else 1
            specs.append(ConvSpec(f"s{stage}.{i}.c1", hw, cin, cmid, 1, 1))
            specs.append(ConvSpec(f"s{stage}.{i}.c2", hw, cmid, cmid, 3, stride))
            hw2 = -(-hw // stride)
            specs.append(ConvSpec(f"s{stage}.{i}.c3", hw2, cmid, cout, 1, 1))
            if i == 0:
                specs.append(ConvSpec(f"s{stage}.{i}.proj", hw, cin, cout, 1,
                                      stride))
            hw = hw2
            cin = cout
    return specs


from chip_kernels import _time_steps  # the differential forced-fetch timing


def _time_fn(fn, *args) -> float:
    """Median seconds per call via ``chip_kernels._time_steps`` (one timing
    methodology across both perf tools)."""
    out = fn(*args)  # warmup/compile
    np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]

    def run_n(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0]).ravel()[:1]  # forced D2H
        return time.perf_counter() - t0

    dt, n = _time_steps(run_n)
    return max(dt, 1e-9) / n


def bench_conv(spec: ConvSpec, batch: int) -> dict:
    import functools

    from jax import lax

    dn = lax.conv_dimension_numbers((1, 1, 1, 1), (1, 1, 1, 1),
                                    ("NHWC", "HWIO", "NHWC"))

    @jax.jit
    def fwd_bwd(x, w):
        def loss(x, w):
            # bf16 in/out like the model's ConvBN (MXU accumulates f32
            # internally); the f32 cast sits where BatchNorm does.
            y = lax.conv_general_dilated(
                x, w, (spec.stride, spec.stride), "SAME",
                dimension_numbers=dn, feature_group_count=spec.groups)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        l, grads = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
        return l, grads

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, spec.in_hw, spec.in_hw, spec.cin)
                    .astype(np.float32), jnp.bfloat16)
    w = jnp.asarray(rng.randn(spec.k, spec.k, spec.cin // spec.groups,
                              spec.cout).astype(np.float32) * 0.05,
                    jnp.bfloat16)
    dt = _time_fn(fwd_bwd, x, w)

    # In-place A/B arms: the round-3 alternative implementations, timed with
    # the identical fwd+bwd harness so the columns are directly comparable.
    variants = {}
    if (spec.groups > 1 and spec.groups == spec.cin == spec.cout
            and spec.k == 3 and spec.stride == 1
            and jax.default_backend() != "cpu"):  # the interpreter is not timed
        from ddw_tpu.ops.depthwise_conv import depthwise_conv3x3

        @jax.jit
        def dw_fwd_bwd(x, w3):
            def loss(x, w3):
                y = depthwise_conv3x3(x, w3, impl="pallas")
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return jax.value_and_grad(loss, argnums=(0, 1))(x, w3)

        variants["pallas_dw"] = _time_fn(dw_fwd_bwd, x, w[:, :, 0, :]) * 1e3
    if (spec.groups == 1 and spec.stride == 2 and spec.k % 2 == 1
            and spec.cin <= 4 and spec.in_hw % 2 == 0):
        from ddw_tpu.ops.s2d_conv import space_to_depth_conv

        @jax.jit
        def s2d_fwd_bwd(x, w):
            def loss(x, w):
                y = space_to_depth_conv(x, w)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

        variants["s2d_stem"] = _time_fn(s2d_fwd_bwd, x, w) * 1e3

    flops = spec.flops(batch)
    bts = spec.bytes_moved(batch)
    t_compute = flops / (PEAK_TFLOPS * 1e12)
    t_memory = bts / (HBM_GBPS * 1e9)
    bound = max(t_compute, t_memory)
    return {
        "variants": variants,
        "spec": spec,
        "ms": dt * 1e3,
        "tflops": flops / dt / 1e12,
        "mfu": flops / dt / 1e12 / PEAK_TFLOPS,
        "gbps": bts / dt / 1e9,
        "ai": flops / bts,  # arithmetic intensity, flops/byte
        "bound_ms": bound * 1e3,
        "bound_kind": "mem" if t_memory > t_compute else "mxu",
        "vs_bound": dt / bound,  # 1.0 = at roofline
    }


_MODELS = {
    "mobilenet_v2": mobilenet_v2_convs,
    "resnet50": resnet50_convs,
}


def profile_model(name: str, batch: int, img: int):
    if name not in _MODELS:
        raise KeyError(f"unknown model {name!r} (have {sorted(_MODELS)})")
    specs = _MODELS[name](img)
    # collapse identical shapes (repeat blocks) and weight by count
    from collections import Counter

    uniq = Counter((s.in_hw, s.cin, s.cout, s.k, s.stride, s.groups)
                   for s in specs)
    rep = {}
    for s in specs:
        rep.setdefault((s.in_hw, s.cin, s.cout, s.k, s.stride, s.groups), s)

    rows = []
    for key, count in uniq.items():
        r = bench_conv(rep[key], batch)
        r["count"] = count
        rows.append(r)
        # Incremental record on stderr: a time-limit kill mid-profile would
        # otherwise lose every row of the model.
        s = r["spec"]
        alt = "".join(f" {k}={v:.3f}ms" for k, v in r["variants"].items())
        print(f"[prof] {name} {s.name} x{count} {s.in_hw}²x{s.cin}->{s.cout}"
              f" k{s.k}s{s.stride}g{s.groups}: {r['ms']:.3f}ms"
              f" {r['tflops']:.1f}TF/s {r['gbps']:.0f}GB/s"
              f" bound={r['bound_kind']} x{r['vs_bound']:.2f}{alt}",
              file=sys.stderr, flush=True)
    rows.sort(key=lambda r: -r["ms"] * r["count"])

    total = sum(r["ms"] * r["count"] for r in rows)
    total_bound = sum(r["bound_ms"] * r["count"] for r in rows)
    print(f"\n== {name} batch={batch} img={img} — per-conv fwd+bwd "
          f"(isolated, bf16, f32 accum)")
    print(f"{'layer':<16}{'xN':>4}{'shape':>22}{'ms':>8}{'TF/s':>7}"
          f"{'GB/s':>7}{'AI':>6}{'bound':>6}{'x-over':>7}")
    for r in rows[:18]:
        s = r["spec"]
        shape = f"{s.in_hw}²x{s.cin}->{s.cout}" + (
            f"/dw" if s.groups > 1 else f"/k{s.k}s{s.stride}")
        alt = "".join(f"  {k}={v:.3f}ms({r['ms'] / max(v, 1e-9):.2f}x)"
                      for k, v in r.get("variants", {}).items())
        print(f"{s.name:<16}{r['count']:>4}{shape:>22}{r['ms']:>8.3f}"
              f"{r['tflops']:>7.1f}{r['gbps']:>7.0f}{r['ai']:>6.0f}"
              f"{r['bound_kind']:>6}{r['vs_bound']:>7.2f}{alt}")
    print(f"{'TOTAL(convs)':<16}{'':>4}{'':>22}{total:>8.2f}  "
          f"roofline-bound total {total_bound:.2f} ms "
          f"(x{total / max(total_bound, 1e-9):.2f} over)")
    return rows, total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("models", nargs="*", default=["mobilenet_v2", "resnet50"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--img", type=int, default=224)
    args = ap.parse_args()
    from ddw_tpu.utils.config import require_tpu_or_exit
    kind = require_tpu_or_exit("profile")
    print(f"device: {kind} "
          f"(assumed {PEAK_TFLOPS} TF/s bf16, {HBM_GBPS} GB/s)")
    for m in (args.models or ["mobilenet_v2", "resnet50"]):
        profile_model(m, args.batch, args.img)


if __name__ == "__main__":
    main()
