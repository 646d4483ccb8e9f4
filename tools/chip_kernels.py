"""Chip validation for the Pallas kernels outside the LM train step.

The flash forward/backward kernels run inside ``chip_smoke.py``'s LM leg. The
depthwise 3x3 kernel (``ops/depthwise_conv.py``) and the RDMA ring
(``ops/ring_reduce.py``) are options no default path turns on, so this tool is
what puts them on the device:

1. **depthwise numerics** — fwd + both grads, Pallas (Mosaic-compiled) vs the
   XLA grouped conv at MobileNetV2's stride-1 depthwise shapes; max |err|.
2. **depthwise timing** — fwd and fwd+bwd A/B vs XLA at those shapes
   (the forced-fetch differential, ``_time_steps`` below).
3. **ring** — with >= 2 devices the ring runs over the first two and over all
   of them, is checked against ``lax.psum``, and is timed against it at a
   gradient-sized buffer. With one device there is nothing to run: ``n == 1``
   returns its input before any kernel, so it is reported as skipped.

A kernel Mosaic refuses raises here with the compiler's message (nonzero
exit); nothing is recorded in its place. On the CPU backend the kernels run in
the Pallas interpreter (``DDW_BENCH_SMOKE=1`` shrinks shapes for CI) and no
timing is taken. Prints one JSON line per report: depthwise, then ring
(``--only depthwise|ring`` runs one of them).
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map

from ddw_tpu.utils.config import env_flag

SMOKE = env_flag("DDW_BENCH_SMOKE")
REPEATS = 1 if SMOKE else 3
# Adaptive sizing: grow N until one differential run holds >= this much device
# work, so fixed dispatch/fetch latency stays inside the noise floor.
MIN_MEASURE_S = 0.05 if SMOKE else 1.0
MAX_STEPS = 8 if SMOKE else 1024


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
        # A runner that executes whole megasteps needs n to be a multiple of
        # its chunk. Round up here (doubling preserves it).
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


def _t(fn, *args):
    """Seconds per call via the adaptive differential ``_time_steps`` (a
    fixed small N would be dispatch-jitter-dominated for sub-ms kernels)."""
    def run_n(n):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0]).ravel()[0]
        return time.perf_counter() - t0

    run_n(1)  # warmup
    dt, n = _time_steps(run_n)
    return dt / n


def depthwise_report() -> list[dict]:
    from ddw_tpu.ops.backend import interpret_by_default
    from ddw_tpu.ops.depthwise_conv import depthwise_conv3x3

    timed = not interpret_by_default()      # interpreter timings are not data
    shapes = ([(2, 16, 16, 32)] if SMOKE else
              # MobileNetV2's stride-1 depthwise layers at 224^2, batch 32
              # (one grid step per image: the batch sets the grid, not the
              # kernel Mosaic compiles)
              [(32, 112, 112, 32), (32, 56, 56, 144), (32, 28, 28, 192),
               (32, 14, 14, 384), (32, 14, 14, 576), (32, 7, 7, 960)])
    rng = np.random.RandomState(0)
    rows = []
    for shape in shapes:
        c = shape[-1]
        x = jnp.asarray(rng.randn(*shape), jnp.float32)
        w = jnp.asarray(rng.randn(3, 3, c) * 0.1, jnp.float32)

        def loss(x, w, impl):
            y = depthwise_conv3x3(x, w, impl=impl)
            return jnp.sum(y * y)

        f_p = jax.jit(lambda x, w: depthwise_conv3x3(x, w, impl="pallas"))
        f_x = jax.jit(lambda x, w: depthwise_conv3x3(x, w, impl="xla"))
        g_p = jax.jit(jax.grad(lambda x, w: loss(x, w, "pallas"),
                               argnums=(0, 1)))
        g_x = jax.jit(jax.grad(lambda x, w: loss(x, w, "xla"),
                               argnums=(0, 1)))

        yp, (dxp, dwp) = f_p(x, w), g_p(x, w)
        # numerics reference: the XLA conv at full f32 precision (a TPU's
        # default conv precision multiplies in bf16, which would make XLA
        # the noisy side of the comparison); the timed XLA arm keeps the
        # default, like the model
        with jax.default_matmul_precision("highest"):
            yx = jax.jit(lambda x, w: depthwise_conv3x3(x, w, impl="xla"))(x, w)
            dxx, dwx = jax.jit(jax.grad(
                lambda x, w: loss(x, w, "xla"), argnums=(0, 1)))(x, w)
        scale = float(jnp.max(jnp.abs(yx))) or 1.0
        err = {
            "fwd": float(jnp.max(jnp.abs(yp - yx))) / scale,
            "dx": float(jnp.max(jnp.abs(dxp - dxx))
                        ) / (float(jnp.max(jnp.abs(dxx))) or 1.0),
            "dw": float(jnp.max(jnp.abs(dwp - dwx))
                        ) / (float(jnp.max(jnp.abs(dwx))) or 1.0),
        }
        row = {"shape": list(shape),
               "rel_err": {k: round(v, 8) for k, v in err.items()},
               "numerics_ok": all(v < 1e-4 for v in err.values())}
        if timed:
            row["fwd_ms"] = {"pallas": round(_t(f_p, x, w) * 1e3, 4),
                             "xla": round(_t(f_x, x, w) * 1e3, 4)}
            row["fwdbwd_ms"] = {"pallas": round(_t(g_p, x, w) * 1e3, 4),
                                "xla": round(_t(g_x, x, w) * 1e3, 4)}
        rows.append(row)
        print(f"[kernels] depthwise {shape}: "
              + " ".join(f"{k}={v:.2e}" for k, v in err.items()),
              file=sys.stderr, flush=True)
    return rows


def ring_report() -> dict:
    """The RDMA ring over the first two devices and over all of them:
    checked against ``lax.psum``, and timed against it on a device backend."""
    from jax.sharding import Mesh, PartitionSpec as P

    from ddw_tpu.ops.backend import interpret_by_default
    from ddw_tpu.ops.ring_reduce import ring_all_reduce_pallas

    n_dev = jax.device_count()
    if n_dev < 2:
        return {"skipped": "1 visible device: ring_all_reduce_pallas returns "
                           "its input before any kernel at n=1, so there is "
                           "nothing to compile or run"}
    timed = not interpret_by_default()
    n_rows = 16 if SMOKE else 4096      # 4 MiB f32 per device: gradient-sized
    out = {}
    for n in sorted({2, n_dev}):
        mesh = Mesh(np.array(jax.devices()[:n]), ("r",))

        def mapped(fn):
            return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("r"),
                                     out_specs=P("r"), check_vma=False))

        ring = mapped(lambda v: ring_all_reduce_pallas(v, "r"))
        psum = mapped(lambda v: jax.lax.psum(v, "r"))
        buf = jnp.asarray(
            np.random.RandomState(n).randn(n * n_rows, 256), jnp.float32)
        got, want = np.asarray(ring(buf)), np.asarray(psum(buf))
        err = float(np.max(np.abs(got - want))) / float(np.max(np.abs(want)))
        row = {"rel_err_vs_psum": round(err, 8), "numerics_ok": err < 1e-5,
               "buffer_mib_per_device": round(buf.nbytes / n / 2**20, 3)}
        if timed:
            row["ms"] = {"ring": round(_t(ring, buf) * 1e3, 4),
                         "psum": round(_t(psum, buf) * 1e3, 4)}
        out[f"n{n}"] = row
        print(f"[kernels] ring n={n}: err={err:.2e}", file=sys.stderr,
              flush=True)
    return out


def main():
    import argparse

    from ddw_tpu.ops.backend import interpret_by_default
    from ddw_tpu.utils.compile_cache import enable_compile_cache
    from ddw_tpu.utils.config import require_tpu_or_exit

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=("depthwise", "ring"))
    only = ap.parse_args().only
    kind = require_tpu_or_exit("measure")
    enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": kind, "n": jax.device_count()}
    mode = "interpret" if interpret_by_default() else "mosaic"
    print(f"device: {device} mode: {mode}", file=sys.stderr, flush=True)
    if only != "ring":
        print(json.dumps({"device": device, "mode": mode,
                          "depthwise": depthwise_report()}), flush=True)
    if only != "depthwise":
        print(json.dumps({"device": device, "mode": mode,
                          "ring": ring_report()}), flush=True)


if __name__ == "__main__":
    main()
