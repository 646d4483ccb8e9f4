#!/usr/bin/env python3
"""``sweep.py`` for a cell whose epoch is long: the same seeds-in-one-process
reading of the ``correct`` comparison's numbers (and, with ``--controls``, of
the reference in a lower precision in the program's place), with the traffic's
``steps_per_epoch`` cut to ``--steps-per-epoch``. The comparison is of the
timed path's first two steps, which an epoch's length does not touch (same
step, same batch shape, rows from the same seeded generator); what is cut is
the chip time spent on the three epochs a run needs before it may stop. The
limits in ``cells/<workload>.json`` are set from these readings and then
proved on runs of the cell itself (PERF.md section 2). ``--leaves N`` also
prints the N leaves with the largest gradient gap of every comparison, so that
a limit can be read against the kind of leaf that sets it.

    python3 benchmark/tools/sweep_first_steps.py --workload keyevl2_train_s8192 \\
        --seeds 11,12,13 --steps-per-epoch 2 --controls fp8 --out chiprun_out/sweep.jsonl
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def show_worst_leaves(n: int):
    """Have every comparison of this process print its ``n`` worst leaves
    (``check.norm_gap``'s arithmetic), program or control against the
    reference."""
    import statistics

    from benchmark.harness import check

    compare = check.compare_steps

    def verbose(program: dict, reference: dict) -> dict:
        ref = reference["grad_norms"]
        floor = statistics.median(ref.values())
        gaps = sorted(((abs(program["grad_norms"][k] - r) / max(r, floor), k)
                       for k, r in ref.items()), reverse=True)[:n]
        print("leaves " + json.dumps([[k, round(g, 5)] for g, k in gaps]),
              flush=True)
        return compare(program, reference)

    check.compare_steps = verbose


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps-per-epoch", type=int, default=2)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the controls on the first N seeds only")
    ap.add_argument("--out", default="")
    ap.add_argument("--leaves", type=int, default=0)
    args = ap.parse_args()

    from benchmark import run as bench_run
    from benchmark.harness.manifest import Cell, load_manifest

    cell = Cell(load_manifest(), args.workload)
    cell.traffic = dict(cell.traffic, steps_per_epoch=args.steps_per_epoch)
    devices, peaks = bench_run.find_devices(cell.chips)
    bench_run.configure_cache()
    controls = tuple(c for c in args.controls.split(",") if c)
    if args.leaves:
        show_worst_leaves(args.leaves)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        result = cell.family.run(
            cell, seed, 0.01, False, t0, devices, peaks,     # one epoch of window
            controls=controls if i < args.control_seeds else ())
        ctx = result.pop("ctx")
        row = {"workload": cell.name, "seed": seed,
               "steps_per_epoch": args.steps_per_epoch,
               "correct": result["correct"], "checks": result["numbers"],
               "controls": result["controls"],
               "items_per_s_chip": ctx["window"]["items_per_s"] / ctx["chips"],
               "took_s": time.time() - t0}
        print("sweep " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
