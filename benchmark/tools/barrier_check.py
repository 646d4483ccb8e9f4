#!/usr/bin/env python3
"""Is the trainers' epoch-end metric fetch an honest barrier on this machine?

The window of a training cell is timed between the moments the trainer has
fetched an epoch's mean losses (``fetch_metrics_mean``: one device reduction
and ``device_get``). This drives the cell's own compiled step ten times and
times the ten twice: ended by that fetch, and ended by ``block_until_ready``
on the state. If the fetch returned before the device was done, the first
would read shorter. Run once on the chip; the answer is in PERF.md section 7.

    python3 benchmark/tools/barrier_check.py --workload gpt2m_train_s1024
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    import jax

    from benchmark import run as bench_run
    from benchmark.harness.manifest import Cell, load_manifest
    from ddw_tpu.train.step import fetch_metrics_mean

    cell = Cell(load_manifest(), args.workload)
    devices, _ = bench_run.find_devices(cell.chips)
    bench_run.configure_cache()
    step, state, batch = cell.family.bare_step(cell.config, cell.traffic,
                                               devices)
    key = jax.random.PRNGKey(0)
    state, m = step(state, *batch, key)          # compile or load
    jax.block_until_ready(state)
    out = {}
    for how in ("fetch", "block_until_ready", "fetch", "block_until_ready"):
        losses = []
        t0 = time.perf_counter()
        for _ in range(args.steps):
            state, m = step(state, *batch, key)
            losses.append(m["loss"])
        if how == "fetch":
            fetch_metrics_mean(losses)
        else:
            jax.block_until_ready(state)
        t1 = time.perf_counter()
        jax.block_until_ready(state)             # what was still running
        t2 = time.perf_counter()
        out.setdefault(how, []).append(
            {"timed_s": t1 - t0, "left_after_s": t2 - t1})
    print("barrier_check " + json.dumps(
        {"workload": cell.name, "steps": args.steps, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
