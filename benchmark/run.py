#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. Finds everything about the cell
by name from ``BENCHMARK.json`` (``harness/manifest.py``), refuses to run
without the chips the cell asks for or on a device missing from the peaks
table, and prints one JSON object as its last line: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, the
device's busy seconds and a breakdown.
"""

import time

T_START = time.time()       # set-up is counted from here

import argparse             # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import sys                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def find_devices(chips: int):
    """The first ``chips`` TPU devices and their peaks, or SystemExit."""
    import jax

    from benchmark.harness.peaks import peaks_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found "
                         f"{devices[0].platform!r}; refusing")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, JAX "
                         f"found {len(devices)}; refusing")
    try:
        peaks = peaks_for(devices[0].device_kind)
    except KeyError as e:
        raise SystemExit(f"benchmark: {e.args[0]}")
    return devices[:chips], peaks


def configure_cache():
    import jax

    from ddw_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # keep every program, also the small ones: the second run of a cell in a
    # checkout has to find them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def attribute_gaps(reduced: dict, ctx: dict) -> list:
    """The longest idle gaps, each named by where it lies among the train
    step's executions on the device's own clock (``trace_reduce.place_gap``)
    and by what the host did for that step: the length of the program's
    ``train_chain`` span of the same dispatch — waiting for the batch, the
    enqueue and back-pressure together. The k-th execution in the traced
    epoch is the k-th span that starts in it."""
    traced = ctx["traced"]
    lo, hi = traced["wall_start"] * 1e6, traced["wall_end"] * 1e6
    chains = [s for s in ctx["spans"]
              if s["name"] == "train_chain" and lo <= s["ts"] <= hi]
    out = []
    for start, end, dev, at in reduced["gaps"]:
        if not at:
            label = "unplaced"
        elif at["where"] == "after_last":
            label = (f"after the epoch's last {at['module']} (validation, "
                     f"epoch-end fetch, next epoch's first dispatch)")
        else:
            label = f"{at['where']} {at['module']} {at['step']}/{at['of']}"
            if len(chains) == at["of"]:
                host_ms = chains[at["step"] - 1]["dur"] / 1e3
                label += f", whose train_chain took {host_ms:.1f} ms on the host"
        out.append([f"{label} [{dev}]", (end - start) / 1e9])
    return out


def rehearse(workload: str, seed: int, seconds: float, trace: bool,
             tiny: dict) -> dict:
    """Everything of a run but the look for a chip, at the ``tiny`` sizes, on
    whatever devices JAX has (the CPU, in the tests). Reports what was
    compared and counted, and no device metric."""
    import jax

    from benchmark.harness.manifest import Cell, load_manifest

    cell = Cell(load_manifest(), workload)
    result = cell.family.run(cell, seed, seconds, trace, time.time(),
                             jax.devices()[:cell.chips], None, tiny=tiny)
    ctx = result.pop("ctx")
    return {"rehearsal": True, "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "checks": result["numbers"], "window": ctx["window"],
            "record": ctx["record"], "spans": len(ctx["spans"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="copy the traced run's .xplane.pb into this directory")
    args = ap.parse_args(argv)

    from benchmark.harness import trace_reduce
    from benchmark.harness.manifest import Cell, load_manifest

    cell = Cell(load_manifest(), args.workload)
    devices, peaks = find_devices(cell.chips)
    cache_dir = configure_cache()
    print(f"benchmark: {cell.name} on {len(devices)} x "
          f"{devices[0].device_kind!r}, seed {args.seed}, window "
          f"{args.seconds} s, trace {args.trace}, compile cache {cache_dir}",
          flush=True)

    result = cell.family.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_START, devices, peaks,
                             keep_trace=args.keep_trace)
    ctx = result.pop("ctx")
    if ctx["record"] is not None:
        ctx["reduced"] = trace_reduce.reduce(ctx["record"])

    listed = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in listed:
        value = cell.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    w = ctx["window"]
    print(f"benchmark: window {w['epochs']} epochs, {w['steps']} steps in "
          f"{w['span_s']:.3f} s; {w['items_per_s'] / ctx['chips']:.1f} "
          f"items/s/chip; epochs {[round(x, 3) for x in w['epoch_s']]}",
          flush=True)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": ctx["peak_bytes"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device,
            "checks": result["numbers"]}
    if args.trace:
        red = ctx["reduced"]
        device["busy_s"] = red["busy_mean_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        line["breakdown"] = {
            # the five families of operation that took most time (name ends in
            # *), then the five single operations; seconds in the traced
            # window, a mean over the chips
            "device_ops": [[n + "*", d / 1e9]
                           for n, d in red["top_families"][:5]]
            + [[n, d / 1e9] for n, d in red["top_ops"][:5]],
            "idle_gaps": attribute_gaps(red, ctx)}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
