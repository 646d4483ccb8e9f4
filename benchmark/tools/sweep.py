#!/usr/bin/env python3
"""Several seeds of one cell in ONE process (set-up is most of a run), each
with a short window, to read what the ``correct`` comparison's numbers are on
sound runs — and, with ``--controls``, what they are when the reference is put
in the program's place in a lower precision. The limits in
``cells/<workload>.json`` are set from these readings (PERF.md section 2).

    python3 benchmark/tools/sweep.py --workload gpt2m_train_s1024 \\
        --seeds 11,12,13 --seconds 3 --controls fp8 --out chiprun_out/sweep.jsonl
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the controls on the first N seeds only")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    from benchmark import run as bench_run
    from benchmark.harness.manifest import Cell, load_manifest

    cell = Cell(load_manifest(), args.workload)
    devices, peaks = bench_run.find_devices(cell.chips)
    bench_run.configure_cache()
    controls = tuple(c for c in args.controls.split(",") if c)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        result = cell.family.run(
            cell, seed, args.seconds, False, t0, devices, peaks,
            controls=controls if i < args.control_seeds else ())
        ctx = result.pop("ctx")
        row = {"workload": cell.name, "seed": seed,
               "correct": result["correct"], "checks": result["numbers"],
               "controls": {p: {k: v for k, v in n.items()}
                            for p, n in result["controls"].items()},
               "items_per_s_chip": ctx["window"]["items_per_s"] / ctx["chips"],
               "setup_s_in_process": ctx["setup_s"],
               "took_s": time.time() - t0}
        print("sweep " + json.dumps(row), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
