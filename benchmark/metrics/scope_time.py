"""The device's time by the program's own scopes: the device trace joined
with the ``step_scopes`` table the program records under a tracer
(``ddw_tpu/obs/step_scopes.py``; the span of that name in ``ctx["spans"]``,
whose arguments map every instruction of the compiled train step to the name
stack it was traced under). Shared by the ``scope_*_ms`` readers and
``step_recompute_ms``; which scope is which metric's is data,
``scope_time.json`` beside this file.

What is counted, on each chip:

- only operations that start inside an execution of the train step's module
  (the table's ``module``, among ``record["modules"]``): the validation
  batch's operations and the small programs' belong to no scope;
- a ``while``, ``conditional`` or ``call`` for its SELF time: its interval
  less those of the operations of its body that the trace shows inside it
  (the table's ``inside``), so nothing is counted twice;
- an operation goes to the INNERMOST layer scope of its path (``layers`` in
  the JSON), whatever step-level scope (``fwd_bwd``) or module name lies
  around it; one whose path holds no layer scope is ``other``; one the
  compiler gave no name stack takes the scope of the loop or branch it runs
  in, and is ``unnamed`` where there is none (copies and slices the compiler
  added at the step's top level);
- a fused operation goes where its own ``op_name`` says, which is its
  root's: a norm fused into the product after it counts with that product.
  That is the limit of a reading by instruction;
- per optimizer step of the traced epoch (``ctx["traced"]["steps"]``; the
  module's executions where there is no such count), a mean over the chips,
  as ``attention_kernel_ms`` is.

Every time is also split by the pass it ran in, the first part of a path:
``fwd``, ``bwd`` (transposed), ``remat`` (a checkpointed block's forward made
again in the backward pass). ``step_recompute_ms`` is the ``remat`` column's
sum.

A cell lists a scope's metric in ``BENCHMARK.json`` where the scope takes 2 %
or more of its step; ``scope_other_ms`` is the step's operation time less the
cell's listed scope metrics and ``scope_unnamed_ms``, so it also holds the
layer scopes too small for a line of their own, and the metrics of a cell add
up to the step's operation time.

Nothing to read (None) where the program recorded no table (a parent without
``obs/step_scopes.py``), or where no operation of the step lies in the scope.

``python3 -m benchmark.metrics.scope_time DIR`` prints the whole table for a
``TrainCfg.trace_dir`` directory (the profile and ``train_spans.trace.json``
beside it): every scope by pass, ms an execution of the step.
"""

from __future__ import annotations

import bisect
import functools
import json
import os

from benchmark.harness.manifest import load_manifest

HERE = os.path.dirname(os.path.abspath(__file__))
OTHER, UNNAMED = "other", "unnamed"
PASSES = ("fwd", "bwd", "remat")


@functools.lru_cache(maxsize=None)
def scope_map() -> dict:
    """``{"metrics": {metric: [scope, ...]}, "layers": [scope, ...]}``, read
    once a process."""
    with open(os.path.join(HERE, "scope_time.json")) as f:
        data = json.load(f)
    data["layers"] = sorted({s for scopes in data["metrics"].values()
                             for s in scopes} | set(data["unmetered"]))
    return data


def find_table(spans: list) -> dict | None:
    """The arguments of the ``step_scopes`` span, or None."""
    for ev in spans or ():
        if ev.get("name") == "step_scopes" and "ops" in ev.get("args", {}):
            return ev["args"]
    return None


def layer_of(path: str, layers) -> tuple:
    """``(layer scope | "other", pass)`` of one scope path."""
    which, *parts = path.split("/")
    for part in reversed(parts):
        if part in layers:
            return part, which
    return OTHER, which


def join(record: dict, table: dict, layers, steps: int | None = None) -> dict:
    """``{"scopes": {scope: {pass: ms}}, "total_ms", "busy_ms", "steps"}``:
    self times by layer scope and pass, their sum, and the union of the
    same operations' intervals (what the sum must close on), each per step
    and a mean over the chips."""
    ops, inside = table["ops"], table["inside"]
    where = [layer_of(p, layers) for p in table["scopes"]]
    acc: dict = {}
    total = busy = runs_seen = 0
    for dev, events in record["devices"].items():
        runs = sorted([s, s + d] for name, s, d in
                      record.get("modules", {}).get(dev, ())
                      if name == table["module"])
        if not runs:
            continue
        starts = [r[0] for r in runs]
        runs_seen = max(runs_seen, len(runs))

        def in_step(s):
            i = bisect.bisect_right(starts, s) - 1
            return i >= 0 and s < runs[i][1]

        mine = sorted(((s, -d, name) for name, s, d in events if in_step(s)))
        open_loops: list = []       # [end, body's names, (scope, pass)]
        last_end = 0
        for s, neg_d, name in mine:
            d = -neg_d
            while open_loops and open_loops[-1][0] <= s:
                open_loops.pop()
            idx = ops.get(name, -1)
            place = where[idx] if idx >= 0 else (UNNAMED, "fwd")
            if open_loops and name in open_loops[-1][1]:
                parent = open_loops[-1][2]
                if idx < 0:
                    place = parent      # the loop's, where it has no own
                acc[parent] = acc.get(parent, 0) - d
            else:
                # a top-level operation of the step (or one beside a loop
                # that is not of its body): the union grows by what it adds
                busy += max(0, s + d - max(s, last_end))
                last_end = max(last_end, s + d)
                total += d
            acc[place] = acc.get(place, 0) + d
            if name in inside:
                open_loops.append([s + d, set(inside[name]), place])
    if not runs_seen:
        return {}
    n_dev = len(record["devices"])
    per = 1e6 * n_dev * (steps or runs_seen)
    scopes: dict = {}
    for (scope, which), ns in acc.items():
        scopes.setdefault(scope, dict.fromkeys(PASSES, 0.0))[which] += ns / per
    return {"scopes": scopes, "total_ms": total / per, "busy_ms": busy / per,
            "steps": steps or runs_seen}


def joined(ctx: dict) -> dict:
    """``join`` of a benchmark run, made once and kept on ``ctx``."""
    if "_scope_time" not in ctx:
        record, traced = ctx.get("record"), ctx.get("traced") or {}
        table = find_table(ctx.get("spans"))
        ctx["_scope_time"] = (
            join(record, table, set(scope_map()["layers"]),
                 traced.get("steps"))
            if record and record.get("devices") and table else {})
    return ctx["_scope_time"]


def scope_ms(times: dict, scopes) -> float | None:
    """Summed time of ``scopes`` over the passes; None where none ran."""
    found = [sum(times["scopes"][s].values()) for s in scopes
             if s in times.get("scopes", {})]
    return sum(found) if found else None


def listed(cell: str) -> list:
    """The scope metrics ``BENCHMARK.json`` lists for ``cell``."""
    named = scope_map()["metrics"]
    return [m["name"] for m in load_manifest()["per_layer"]
            if m["name"] in named and cell in m.get("workloads", [cell])]


def read_metric(ctx: dict, metric: str) -> float | None:
    """One ``scope_*_ms`` or ``step_recompute_ms`` of a benchmark run."""
    times = joined(ctx)
    if not times:
        return None
    named = scope_map()["metrics"]
    if metric in named:
        return scope_ms(times, named[metric])
    if metric == "step_recompute_ms":
        return sum(by["remat"] for by in times["scopes"].values()) or None
    unnamed = scope_ms(times, [UNNAMED]) or 0.0
    if metric == "scope_unnamed_ms":
        return unnamed
    if metric == "scope_other_ms":
        own = sum(scope_ms(times, named[m]) or 0.0
                  for m in listed(ctx["cell"]))
        return times["total_ms"] - own - unnamed
    raise KeyError(f"no scope metric {metric!r}")


def main(argv=None) -> int:
    import argparse

    from benchmark.harness import trace_reduce
    from ddw_tpu.obs.trace import load_events

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir", help="a TrainCfg.trace_dir directory")
    args = ap.parse_args(argv)
    table = find_table(load_events(os.path.join(args.trace_dir,
                                                 "train_spans.trace.json")))
    if table is None:
        raise SystemExit("no step_scopes span in train_spans.trace.json: "
                         "the fit ran without the table")
    record = trace_reduce.load_xplane(trace_reduce.find_xplane(args.trace_dir))
    times = join(record, table, set(scope_map()["layers"]))
    if not times:
        raise SystemExit(f"the profile holds no execution of {table['module']}")
    print(f"{table['module']}: {times['steps']} executions, "
          f"{times['total_ms']:.3f} ms of operations an execution (union "
          f"{times['busy_ms']:.3f}); {len(table['ops'])} operations in the "
          f"table, {table['unnamed_ops']} without a name stack")
    print(f"{'scope':<20}" + "".join(f"{p:>10}" for p in PASSES)
          + f"{'ms':>10}{'%':>7}")
    rows = sorted(times["scopes"].items(), key=lambda r: -sum(r[1].values()))
    for scope, by in rows:
        ms = sum(by.values())
        print(f"{scope:<20}" + "".join(f"{by[p]:>10.3f}" for p in PASSES)
              + f"{ms:>10.3f}{100 * ms / times['total_ms']:>7.2f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
