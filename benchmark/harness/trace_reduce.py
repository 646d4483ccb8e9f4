"""From a profiler trace to the numbers the per-layer readers take.

Two steps, so that the arithmetic can be tested without a chip. ``load_xplane``
turns the profiler's ``.xplane.pb`` into a plain record::

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "modules": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...}}

and ``reduce`` turns such a record (a recorded one is checked in under
``tests/``) into busy, idle, collective and per-operation times.

Definitions. An operation is an event of a device plane's ``XLA Ops`` line
(the ``Async XLA Ops`` line, which holds the in-flight spans of asynchronous
copies, is not). A module is an event of the ``XLA Modules`` line: one
execution of one compiled program, so the train step's executions mark the
steps on the device's own clock.
Busy is the union of the operations' intervals on one device. The traced window
runs from the first operation's start to the last one's end over all devices
(the profiler's own start and stop are outside it). Idle share is
1 - busy / window on the worst device. A collective is an operation whose name
starts with one of ``COLLECTIVES`` (its ``-start`` / ``-done`` halves
included); its exposed time is the part of its intervals in which no other
operation runs on that device.
"""

from __future__ import annotations

import glob
import os

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        or glob.glob(os.path.join(trace_dir, "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, modules = {}, {}
    for plane in data.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                devices[plane.name] = [
                    [op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events]
            elif line.name == MODULES_LINE:
                modules[plane.name] = [
                    [module_name(ev.name), int(ev.start_ns),
                     int(ev.duration_ns)] for ev in line.events]
    return {"devices": devices, "modules": modules}


def module_name(event_name: str) -> str:
    """``jit__step(4061511910469569733)`` -> ``jit__step``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """The trace names an operation by its whole HLO line,
    ``%fusion.12 = f32[8,1024]{...} fusion(...)``; keep ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: list) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def length(intervals: list) -> int:
    return sum(end - start for start, end in intervals)


def subtract(a: list, b: list) -> list:
    """The part of merged intervals ``a`` that merged intervals ``b`` leave."""
    out, j = [], 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``; names without a numeric suffix stay."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _by_family(by_name: dict, n_dev: int) -> list:
    fam: dict = {}
    for n, d in by_name.items():
        fam[op_family(n)] = fam.get(op_family(n), 0) + d / n_dev
    return sorted(([n, d] for n, d in fam.items()), key=lambda r: -r[1])


def reduce(record: dict, top: int = 10) -> dict:
    devices = record["devices"]
    if not devices:
        raise ValueError("the trace holds no device operations")
    t0 = min(s for evs in devices.values() for _, s, _ in evs)
    t1 = max(s + d for evs in devices.values() for _, s, d in evs)
    window = t1 - t0
    per_device, by_name, gaps = {}, {}, []
    for dev, evs in devices.items():
        all_iv = union([[s, s + d] for _, s, d in evs])
        coll_iv = union([[s, s + d] for n, s, d in evs if is_collective(n)])
        other_iv = union([[s, s + d] for n, s, d in evs
                          if not is_collective(n)])
        per_device[dev] = {
            "busy_ns": length(all_iv),
            "collective_ns": sum(d for n, _, d in evs if is_collective(n)),
            "collective_exposed_ns": length(subtract(coll_iv, other_iv)),
        }
        for n, _, d in evs:
            by_name[n] = by_name.get(n, 0) + d
        edges = [[t0, t0]] + all_iv + [[t1, t1]]
        gaps += [[edges[i][1], edges[i + 1][0], dev]
                 for i in range(len(edges) - 1)
                 if edges[i + 1][0] > edges[i][1]]
    n_dev = len(devices)
    worst = lambda key: max(v[key] for v in per_device.values())
    return {
        "t0_ns": t0, "window_ns": window, "devices": n_dev,
        "busy_mean_ns": sum(v["busy_ns"] for v in per_device.values()) / n_dev,
        "busy_min_ns": min(v["busy_ns"] for v in per_device.values()),
        "collective_ns": worst("collective_ns"),
        "collective_exposed_ns": worst("collective_exposed_ns"),
        "top_ops": sorted(([n, d / n_dev] for n, d in by_name.items()),
                          key=lambda r: -r[1])[:top],
        "top_families": _by_family(by_name, n_dev)[:top],
        "gaps": [g + [place_gap(g, record.get("modules", {}).get(g[2], []))]
                 for g in sorted(gaps, key=lambda g: g[0] - g[1])[:top]],
    }


def place_gap(gap: list, modules: list) -> dict:
    """Where an idle gap lies among the executions of the train step on its
    own chip, on the device's clock: ``{"step": k, "of": n, "where": ...}``
    with ``where`` one of ``inside`` (within execution k), ``before`` (after
    execution k-1 has ended and before k starts) or ``after_last``. The train
    step is the module with the most device time."""
    total: dict = {}
    for name, _, dur in modules:
        total[name] = total.get(name, 0) + dur
    if not total:
        return {}
    step = max(total, key=total.get)
    runs = sorted([s, s + d] for name, s, d in modules if name == step)
    mid = (gap[0] + gap[1]) / 2
    for k, (start, end) in enumerate(runs, start=1):
        if mid < start:
            return {"module": step, "step": k, "of": len(runs),
                    "where": "before"}
        if mid <= end:
            return {"module": step, "step": k, "of": len(runs),
                    "where": "inside"}
    return {"module": step, "step": len(runs), "of": len(runs),
            "where": "after_last"}
