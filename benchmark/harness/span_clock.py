"""The program's spans and the device's operations on one time axis.

The two are stamped on different clocks. A span of the program's ``Tracer``
(``ctx["spans"]``) carries microseconds since the Unix epoch. An operation of
the profile (``ctx["record"]``, ``trace_reduce.load_xplane``) carries
``start_ns`` counted from the profiler session's start
(``tools/clock_check.py`` found that on the chip, PERF.md section 7d), and the
record does not keep the session's start. The profile runs with the host
tracer off, so no event of the profile itself says where the host was.

So the clocks are joined by two moments that both sides see:

- the **fetch anchor**: the traced epoch's ``epoch_fetch`` span ends when the
  host has read the epoch's losses, which is just after the last device
  operation before it has ended. ``host - device`` there is the offset plus
  the read's latency (and ``get_lr``), so it is an upper bound of the offset;
- the **dispatch anchor**: the traced epoch's first ``dispatch`` span ENDS as
  the first execution of the train step (the module with the most device
  time) starts. The step call spends its time on the host before the launch
  (7 ms for GPT-2 medium's 900 buffers, 4.7 ms for ViT-B/16) and returns as
  the launch is made: on the chip the two anchors agree to 0.1 ms when the
  span's end is taken, and miss by the call's whole length when its start is
  (PERF.md section 7d). ``dispatch_start_offset_ns`` keeps the start's
  reading: it is a true lower bound of the offset, a loose one.

**The one rule**: spans are shifted onto the profile's clock by the fetch
anchor. The dispatch anchor, shifted the same way, then misses by the
**residual**, which is computed in every run and reported as
``clock_residual_ms``: two independent moments that agree to it. An idle gap
shorter than the residual cannot be given to a span with certainty; the long
ones can.

Idle time is then split by what the ``train`` thread was in. The window and
the chip are ``device_idle_pct``'s own (first operation's start to last
operation's end over all chips; the chip that was busy least), so the four
shares add up to it.
"""

from __future__ import annotations

import statistics

from benchmark.harness import trace_reduce

TRAIN_TID = "train"
DATA = ("data_wait", "val_data_wait")
DISPATCH = ("dispatch", "val_dispatch", "train_chain")
KINDS = ("data_wait", "dispatch", "boundary", "unattributed")


def traced_spans(ctx: dict, name: str | None = None, tid: str | None = None
                 ) -> list:
    """The program's spans that start inside the traced epoch's wall
    interval (``host_chain_ms``'s own filter), oldest first."""
    traced = ctx.get("traced")
    if not traced:
        return []
    lo, hi = traced["wall_start"] * 1e6, traced["wall_end"] * 1e6
    return sorted((s for s in ctx["spans"]
                   if lo <= s["ts"] <= hi and s["ph"] == "X"
                   and (name is None or s["name"] == name)
                   and (tid is None or s["tid"] == tid)),
                  key=lambda s: s["ts"])


def median_ms(ctx: dict, name: str):
    """Median length of the traced epoch's spans of that name, or None."""
    durs = [s["dur"] for s in traced_spans(ctx, name)]
    return statistics.median(durs) / 1e3 if durs else None


def step_module(modules: dict) -> str | None:
    """The train step: the module with the most device time (as
    ``trace_reduce.place_gap`` has it)."""
    total: dict = {}
    for events in modules.values():
        for name, _, dur in events:
            total[name] = total.get(name, 0) + dur
    return max(total, key=total.get) if total else None


def align(ctx: dict) -> dict | None:
    """``{"base_us", "offset_ns", "dispatch_offset_ns",
    "dispatch_start_offset_ns", "residual_ns"}`` (``offset_ns`` is the fetch
    anchor's) or None where the program has no ``dispatch`` or
    ``epoch_fetch`` span in the traced epoch (a program older than the
    spans) or the record no operation. A host time ``ts`` (microseconds)
    lies at ``(ts - base_us) * 1e3 - offset_ns`` on the profile's clock."""
    record = ctx.get("record")
    if not record or not record.get("devices"):
        return None
    dispatches = traced_spans(ctx, "dispatch", TRAIN_TID)
    fetches = traced_spans(ctx, "epoch_fetch", TRAIN_TID)
    step = step_module(record.get("modules", {}))
    if not dispatches or not fetches or step is None:
        return None
    base_us = ctx["traced"]["wall_start"] * 1e6
    rel_ns = lambda us: (us - base_us) * 1e3
    first_run = min(s for events in record["modules"].values()
                    for name, s, _ in events if name == step)
    start_offset = rel_ns(dispatches[0]["ts"]) - first_run
    dispatch_offset = start_offset + dispatches[0]["dur"] * 1e3
    fetch_end = rel_ns(fetches[0]["ts"] + fetches[0]["dur"])
    # the last operation that had ended when the fetch returned; "before" is
    # judged on the dispatch span's start, a clock that runs early and so
    # never leaves out an operation the fetch waited for
    last_end = max((s + d for events in record["devices"].values()
                    for _, s, d in events
                    if s + d + start_offset <= fetch_end), default=None)
    if last_end is None:
        return None
    fetch_offset = fetch_end - last_end
    return {"base_us": base_us, "offset_ns": fetch_offset,
            "dispatch_offset_ns": dispatch_offset,
            "dispatch_start_offset_ns": start_offset,
            "residual_ns": abs(fetch_offset - dispatch_offset)}


def depths(spans: list) -> dict:
    """``{span id: depth}`` from the events' parent ids; a span whose parent
    is not among them is a root."""
    parent = {s["span"]: s["parent"] for s in spans}
    out: dict = {}

    def depth(sid):
        if sid not in out:
            up = parent[sid]
            out[sid] = depth(up) + 1 if up in parent else 0
        return out[sid]

    for sid in parent:
        depth(sid)
    return out


def innermost(spans: list) -> list:
    """``[start, end, name, depth]`` spans of one thread as disjoint sorted
    ``[start, end, name]`` pieces, each named by the deepest span that covers
    it. Time no span covers is left out. Depth comes from the parent ids and
    not from which interval holds which: two spans that share a stamp (one
    ends where the next starts) overlap by a rounding of their doubles, and
    the later one is still the earlier one's sibling."""
    edges = sorted({t for s in spans for t in s[:2]})
    out: list = []
    for a, b in zip(edges, edges[1:]):
        covering = [s for s in spans if s[0] <= a and b <= s[1]]
        if not covering:
            continue
        name = max(covering, key=lambda s: s[3])[2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return out


def overlaps(intervals: list, pieces: list) -> list:
    """For each of the sorted disjoint ``[start, end]`` intervals, what the
    sorted disjoint ``[start, end, name]`` pieces cover of it:
    ``[{name: ns, ...}, ...]`` in the intervals' order."""
    out, j = [], 0
    for start, end in intervals:
        while j < len(pieces) and pieces[j][1] <= start:
            j += 1
        got: dict = {}
        k = j
        while k < len(pieces) and pieces[k][0] < end:
            a, b, name = pieces[k]
            got[name] = got.get(name, 0) + min(b, end) - max(a, start)
            k += 1
        out.append(got)
    return out


def kind_of(name: str) -> str:
    if name in DATA:
        return "data_wait"
    return "dispatch" if name in DISPATCH else "boundary"


def idle_by_span(ctx: dict) -> dict | None:
    """The idle time of ``device_idle_pct``'s chip and window, by the span the
    ``train`` thread was in. Kept on ``ctx`` so that the readers share one
    pass::

        {"window_ns", "idle_ns", "by_kind": {kind: ns}, "by_name": {name: ns},
         "gaps": [[start_ns, end_ns, {name: ns}], ...] longest first,
         "clock": what align() returned}
    """
    if "_idle_by_span" in ctx:
        return ctx["_idle_by_span"]
    ctx["_idle_by_span"] = out = _idle_by_span(ctx)
    return out


def _idle_by_span(ctx: dict) -> dict | None:
    clock = align(ctx)
    if clock is None:
        return None
    devices = ctx["record"]["devices"]
    # device_idle_pct's own window, from the same reduction
    t0 = ctx["reduced"]["t0_ns"]
    t1 = t0 + ctx["reduced"]["window_ns"]
    busy = {dev: trace_reduce.union([[s, s + d] for _, s, d in evs])
            for dev, evs in devices.items()}
    chip = min(busy, key=lambda dev: trace_reduce.length(busy[dev]))
    idle = trace_reduce.subtract([[t0, t1]], busy[chip])
    on_profile = lambda us: (us - clock["base_us"]) * 1e3 - clock["offset_ns"]
    train = [s for s in ctx["spans"]
             if s["tid"] == TRAIN_TID and s["ph"] == "X"]
    depth = depths(train)
    placed = [[on_profile(s["ts"]), on_profile(s["ts"] + s["dur"]),
               s["name"], depth[s["span"]]] for s in train]
    # the spans that reach into the window: the traced epoch's and its
    # neighbours', not the whole run's
    pieces = innermost([p for p in placed if p[1] > t0 and p[0] < t1])
    by_kind = dict.fromkeys(KINDS, 0)
    by_name: dict = {}
    gaps = []
    for (start, end), got in zip(idle, overlaps(idle, pieces)):
        covered = 0
        for name, ns in got.items():
            by_kind[kind_of(name)] += ns
            by_name[name] = by_name.get(name, 0) + ns
            covered += ns
        by_kind["unattributed"] += (end - start) - covered
        gaps.append([start, end, got])
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"window_ns": t1 - t0, "idle_ns": trace_reduce.length(idle),
            "chip": chip, "by_kind": by_kind, "by_name": by_name,
            "gaps": gaps[:10], "clock": clock}


def idle_share_pct(ctx: dict, kind: str):
    """One of the four shares, in percent of the traced window."""
    found = idle_by_span(ctx)
    if found is None:
        return None
    return 100.0 * found["by_kind"][kind] / found["window_ns"]
