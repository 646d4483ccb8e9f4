#!/usr/bin/env python3
"""Size a cell's window from its epochs: where ``train_mfu``'s spread comes
from and what a later or longer window would do to it.

    python3 benchmark/tools/epoch_table.py LOG [LOG ...] [--counter NAME]
        [--opens 2,5,8] [--lengths 3,6,9] [--at 2,4,8,12] [--stall 1.1]

Each LOG is the output of one untraced run of ONE cell on a seed of its own,
made with a long ``--seconds`` (``run.py`` prints the window's epoch lengths,
the routed families print ``counters by epoch``, the last line has
``train_mfu``). Epochs are counted from 0, the compiling one, so today's
window opens at epoch 2. From the runs' epochs, and nothing run again:

- the variance of an epoch's ``train_mfu`` over (seed, epoch), the epochs'
  common drift taken out, split into the part between seeds (a seed's mean
  over its epochs) and the part within a run (what is left): a longer window
  averages the second away and not the first;
- the counter's range over the seeds at some epochs: do the seeds converge;
- ``train_mfu`` over windows of some lengths opened at some epochs: median and
  spread the driver's way (``tools/spread.py``), over all the seeds and over
  every six of them (mean and worst), since the driver takes six runs;
- which seeds' ``moe_rows_run_share`` left its floor, and when (a second
  chunk of the dispatch: a step in cost a longer window walks into).
"""

from __future__ import annotations

import argparse
import ast
import itertools
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools.spread import range_drop1  # noqa: E402


def read_log(path: str) -> dict | None:
    """A run's epochs: their lengths in the window, the counters' rows (warm
    epochs included) and the run's ``train_mfu``."""
    rows = epoch_s = mfu = None
    with open(path) as f:
        for line in f:
            if line.startswith("counters by epoch "):
                rows = ast.literal_eval(line[len("counters by epoch "):])
            elif line.startswith("benchmark: window ") and "epochs [" in line:
                epoch_s = ast.literal_eval(line[line.index("epochs [") + 7:])
            elif line.startswith("{") and '"metrics"' in line:
                last = json.loads(line)
                if "train_mfu" in last["metrics"]:
                    mfu = last["metrics"]["train_mfu"]["value"]
    if epoch_s is None or mfu is None:
        return None
    rows = rows or [{}] * (len(epoch_s) + 2)
    warm = len(rows) - len(epoch_s)
    # an epoch's train_mfu: the window's, scaled by the epoch's length
    mean_s = sum(epoch_s) / len(epoch_s)
    return {"path": path, "warm": warm, "rows": rows, "epoch_s": epoch_s,
            "epoch_mfu": [mfu * mean_s / s for s in epoch_s], "train_mfu": mfu}


def window_mfu(run: dict, opens: int, length: int) -> float | None:
    """``train_mfu`` of the window of ``length`` epochs opened at epoch
    ``opens``, or None where the run does not reach."""
    first = opens - run["warm"]
    if first < 0 or first + length > len(run["epoch_s"]):
        return None
    spans = run["epoch_s"][first:first + length]
    mean_s = sum(run["epoch_s"]) / len(run["epoch_s"])
    return run["train_mfu"] * mean_s / (sum(spans) / length)


def variance_shares(runs: list, stall: float = 0.0) -> dict:
    """Between-seed and within-run variance of an epoch's ``train_mfu`` over
    the epochs every run has, each epoch's mean over the seeds taken out.
    With ``stall``, an epoch longer than ``stall`` times its run's median
    epoch (the host held up, not the routing) is left out and counted."""
    n = min(len(r["epoch_mfu"]) for r in runs)
    table, stalls = [], 0
    for r in runs:
        slow = stall * statistics.median(r["epoch_s"]) if stall else None
        table.append([None if slow and s > slow else v
                      for v, s in zip(r["epoch_mfu"][:n], r["epoch_s"])])
        stalls += table[-1].count(None)
    there = lambda xs: [x for x in xs if x is not None]  # noqa: E731
    drift = [statistics.fmean(there(col)) for col in zip(*table)]
    rest = [there([None if v is None else v - d for v, d in zip(row, drift)])
            for row in table]
    between = statistics.pvariance([statistics.fmean(row) for row in rest])
    within = statistics.fmean(statistics.pvariance(row) for row in rest)
    total = between + within
    return {"epochs": n, "seeds": len(runs), "stalls_left_out": stalls,
            "between_seeds": between, "within_run": within,
            "between_share": between / total if total else 0.0,
            "drift_first_to_last": drift[-1] - drift[0]}


def spreads(values: list) -> dict:
    out = {"runs": len(values), "median": statistics.median(values),
           "range_drop1": range_drop1(values)}
    if len(values) > 6:
        sixes = [range_drop1(list(c))
                 for c in itertools.combinations(values, 6)]
        out["sixes_mean"] = statistics.fmean(sixes)
        out["sixes_worst"] = max(sixes)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("logs", nargs="+")
    ap.add_argument("--counter", default="moe_assignments_per_token")
    ap.add_argument("--opens", default="2,5,8")
    ap.add_argument("--lengths", default="3,6,9")
    ap.add_argument("--at", default="2,4,8,12")
    ap.add_argument("--stall", type=float, default=0.0,
                    help="leave epochs over this many times the run's median "
                         "epoch out of the variance (1.1; 0: none)")
    args = ap.parse_args(argv)
    runs = [r for r in map(read_log, args.logs) if r]
    if len(runs) < 2:
        raise SystemExit("epoch_table: needs two runs' logs or more")
    ints = lambda text: [int(x) for x in text.split(",")]  # noqa: E731
    out = {"variance": variance_shares(runs, args.stall),
           "counter_by_seed": {},
           "windows": [], "left_the_floor": {}}
    for epoch in ints(args.at):
        values = [r["rows"][epoch][args.counter] for r in runs
                  if epoch < len(r["rows"]) and args.counter in r["rows"][epoch]]
        if values:
            out["counter_by_seed"][epoch] = {
                "least": min(values), "most": max(values),
                "range": max(values) - min(values), "seeds": len(values)}
    for opens in ints(args.opens):
        for length in ints(args.lengths):
            values = [v for v in (window_mfu(r, opens, length) for r in runs)
                      if v is not None]
            if len(values) >= 2:
                out["windows"].append(dict(opens=opens, length=length,
                                           **spreads(values)))
    for r in runs:
        shares = [row.get("moe_rows_run_share") for row in r["rows"]]
        if shares[0] is not None:
            floor = min(shares)
            over = [i for i, s in enumerate(shares) if s > floor * 1.001]
            out["left_the_floor"][os.path.basename(r["path"])] = {
                "floor": floor, "first_epoch_over": over[0] if over else None,
                "last": shares[-1]}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
