#!/usr/bin/env python3
"""How widely a set of runs of one cell spread, as the driver's check takes
it, so that builders and driver speak of the same numbers.

    python3 benchmark/tools/spread.py [--metric train_mfu] FILE [FILE ...]

Every line of a FILE that is a run's last line (one JSON object with
``metrics``) is a run: a file of kept last lines, or the runs' own logs. All
FILEs together are ONE set; give a set at a time.

Three spreads, each as a share of the median of all the runs:

- ``iqr``: third minus first quartile as ``statistics.quantiles(n=4)`` gives
  them, over all the runs: what a bound is set from (five times the widest)
  and what the driver holds a bound against as too loose.
- ``iqr_drop1``: the same with the run farthest from the median left out:
  what the driver holds against HALF the bound of a new or changed cell (too
  tight) and against the bound when it tells a later PR's two sides apart
  ("the spread is 0.370979 % ... and the bound is 0.319291 %", PR 37: 1.45 % of
  the median; eight seeds of that cell as it then was read 1.44 % this way and
  2.26 % as a range, chip runs of PR 38).
- ``range_drop1``: largest minus smallest, the farthest run left out where
  that narrows it: about 1.4 times ``iqr_drop1`` on six runs, the measure PR
  38's issue asked for. A new routed cell shows it at half the bound or less
  over two sets of six before it is proposed, which leaves the driver's room.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys


def range_drop1(values: list) -> float:
    """Largest minus smallest over the median of all, without the run
    farthest from that median where leaving it out narrows the range."""
    median = statistics.median(values)
    whole = max(values) - min(values)
    if len(values) < 3:
        return whole / median
    rest = without_farthest(values)
    return min(whole, rest[-1] - rest[0]) / median


def without_farthest(values: list) -> list:
    """``values`` without the one farthest from their median."""
    median = statistics.median(values)
    rest = sorted(values)
    rest.remove(max(rest, key=lambda v: abs(v - median)))
    return rest


def iqr(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def iqr_drop1(values: list) -> float:
    """The quartile distance of the runs but the farthest, over the median of
    all."""
    if len(values) < 4:
        return iqr(values)
    q1, _, q3 = statistics.quantiles(without_farthest(values), n=4)
    return (q3 - q1) / statistics.median(values)


def summary(values: list) -> dict:
    median = statistics.median(values)
    return {"runs": len(values), "median": median,
            "iqr": iqr(values) if len(values) >= 2 else 0.0,
            "iqr_drop1": iqr_drop1(values) if len(values) >= 2 else 0.0,
            "range_drop1": range_drop1(values),
            "range": (max(values) - min(values)) / median}


def last_lines(paths: list) -> list:
    """The runs' last lines found in ``paths``, in order."""
    runs = []
    for path in paths:
        with (sys.stdin if path == "-" else open(path)) as f:
            for line in f:
                line = line.strip()
                if line.startswith("{") and '"metrics"' in line:
                    try:
                        runs.append(json.loads(line))
                    except ValueError:
                        pass
    return runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("files", nargs="+")
    ap.add_argument("--metric", default="",
                    help="one metric; every metric of the lines without")
    args = ap.parse_args(argv)
    runs = last_lines(args.files)
    if not runs:
        raise SystemExit("spread: no run's last line in " + str(args.files))
    names = [args.metric] if args.metric else sorted(
        {name for run in runs for name in run["metrics"]})
    wrong = sum(1 for run in runs if not run.get("correct"))
    out = {"runs": len(runs), "not_correct": wrong}
    for name in names:
        values = [run["metrics"][name]["value"] for run in runs
                  if name in run["metrics"]]
        if values:
            out[name] = dict(summary(values), values=values)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
