#!/usr/bin/env python3
"""What does the profile's ``start_ns`` count from, and do the two anchors of
``harness/span_clock.py`` agree?

    python3 benchmark/tools/clock_check.py --workload <cell> [--seed n]
                                           [--seconds s] [--keep-trace DIR]

Runs the cell once with the tracer and the profiler on (as ``run.py --trace
1`` does; ``--seconds`` may be short, the traced epoch is the window's
second) and prints one ``clock_check {...}`` line:

- ``session``: the profile's own ``profile_start_time`` (the ``Task
  Environment`` plane, Unix ns) against the harness's stamp taken when
  ``start_trace`` had returned, and against where the fetch anchor and the
  dispatch anchor (by the span's end, and by its start) put the profile
  clock's zero: what ``start_ns`` counts from;
- ``anchors``: the offsets and the residual; ``chips``: each chip's first
  train-step execution against the earliest one (do the chips' clocks agree);
- ``idle``: ``device_idle_pct``, the four shares and their sum, idle time by
  span name, and the longest gaps with the spans that own them;
- ``host``: medians of ``train_chain``, ``data_wait``, ``dispatch`` in the
  traced epoch and the chain's self time; ``setup``: ``trainer_init_s``,
  ``step_load_s`` against ``first_epoch_s``; ``epoch_s`` of every epoch, the
  traced one marked, for the tracer's cost; ``traced_epoch_spans_ms``: count,
  sum and longest of every span name in the traced epoch.

Run once a cell on the chip (one chip and four); the answers are in PERF.md
section 7d.
"""

import time

T_START = time.time()

import argparse             # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import statistics           # noqa: E402
import sys                  # noqa: E402
import tempfile             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def session_times(xplane: str) -> dict:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane).planes:
        if plane.name == "Task Environment":
            return {k: int(v) for k, v in plane.stats
                    if k in ("profile_start_time", "profile_stop_time")}
    return {}


def report(ctx: dict, read, session: dict) -> dict:
    """The line's content from a traced run's ``ctx`` (``reduced`` filled),
    ``read(metric)`` and the profile's session times."""
    from benchmark.harness import span_clock

    found = span_clock.idle_by_span(ctx)
    if found is None:
        raise SystemExit("clock_check: the run has no dispatch / epoch_fetch "
                         "span or no device operation; nothing to join")
    clock = found["clock"]
    start = session.get("profile_start_time")
    # where an anchor puts the profile clock's zero, against the session's
    # start (both Unix ns)
    zero_ms = lambda offset_ns: None if start is None else (
        clock["base_us"] * 1e3 + offset_ns - start) / 1e6

    step = span_clock.step_module(ctx["record"]["modules"])
    firsts = {dev: min(s for name, s, _ in events if name == step)
              for dev, events in ctx["record"]["modules"].items()}
    earliest = min(firsts.values())

    shares = {kind: 100.0 * ns / found["window_ns"]
              for kind, ns in found["by_kind"].items()}
    chains = span_clock.traced_spans(ctx, "train_chain")
    parts = dict.fromkeys((c.get("span") for c in chains), 0.0)
    for s in span_clock.traced_spans(ctx):
        if s["name"] in ("data_wait", "dispatch") and s.get("parent") in parts:
            parts[s["parent"]] += s["dur"]
    self_ms = [(c["dur"] - parts[c.get("span")]) / 1e3 for c in chains]
    # count, summed and longest length of each span name in the traced epoch
    traced: dict = {}
    for s in span_clock.traced_spans(ctx):
        row = traced.setdefault(s["name"], [0, 0.0, 0.0])
        row[0] += 1
        row[1] += s["dur"] / 1e3
        row[2] = max(row[2], s["dur"] / 1e3)
    by_ms = lambda got: {n: ns / 1e6 for n, ns in sorted(
        got.items(), key=lambda r: -r[1])}
    return {
        "cell": ctx["cell"], "chips": ctx["chips"],
        "session": {
            "profile_start_time_ns": start,
            "wall_start_minus_profile_start_ms": None if start is None
            else (ctx["traced"]["wall_start"] * 1e9 - start) / 1e6,
            "zero_by_fetch_ms": zero_ms(clock["offset_ns"]),
            "zero_by_dispatch_ms": zero_ms(clock["dispatch_offset_ns"]),
            "zero_by_dispatch_start_ms": zero_ms(
                clock["dispatch_start_offset_ns"])},
        "anchors": {"fetch_offset_ms": clock["offset_ns"] / 1e6,
                    "dispatch_offset_ms": clock["dispatch_offset_ns"] / 1e6,
                    "dispatch_start_offset_ms":
                        clock["dispatch_start_offset_ns"] / 1e6,
                    "residual_ms": clock["residual_ns"] / 1e6},
        "chips_first_step_us": {dev: (s - earliest) / 1e3
                                for dev, s in firsts.items()},
        "idle": {"device_idle_pct": read("device_idle_pct"),
                 "shares_pct": shares, "sum_pct": sum(shares.values()),
                 "chip": found["chip"],
                 "by_span_ms": by_ms(found["by_name"]),
                 "longest_gaps": [
                     {"ms": (end - begin) / 1e6,
                      "at_ms": (begin - ctx["reduced"]["t0_ns"]) / 1e6,
                      "owners_ms": by_ms(got)}
                     for begin, end, got in found["gaps"][:5]]},
        "host": {"host_chain_ms": read("host_chain_ms"),
                 "data_wait_ms": read("data_wait_ms"),
                 "dispatch_ms": read("dispatch_ms"),
                 "chain_self_ms": statistics.median(self_ms) if self_ms else None,
                 "loader_batch_ms": read("loader_batch_ms"),
                 "loader_h2d_ms": read("loader_h2d_ms"),
                 "loader_blocked_ms": span_clock.median_ms(ctx,
                                                           "loader_blocked")},
        "setup": {"trainer_init_s": read("trainer_init_s"),
                  "step_load_s": read("step_load_s"),
                  "first_epoch_s": ctx["first_epoch_s"],
                  "setup_s": ctx["setup_s"]},
        # the window's epochs; the profiler starts and stops inside the
        # tracker's report, after the epoch's end was stamped, so the start
        # is in the traced epoch's seconds and the stop in the next one's
        "epoch_s": ctx["window"]["epoch_s"],
        "traced_epoch_index": 1,       # the window's second epoch
        "traced_epoch_spans_ms": traced,
        "spans": len(ctx["spans"]),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 25)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--keep-trace", default="")
    args = ap.parse_args()

    from benchmark import run as bench_run
    from benchmark.harness import trace_reduce
    from benchmark.harness.manifest import Cell, load_manifest

    cell = Cell(load_manifest(), args.workload)
    devices, peaks = bench_run.find_devices(cell.chips)
    bench_run.configure_cache()
    keep = args.keep_trace or tempfile.mkdtemp(prefix="ddw_clock_")
    result = cell.family.run(cell, args.seed, args.seconds, True, T_START,
                             devices, peaks, keep_trace=keep)
    ctx = result["ctx"]
    ctx["reduced"] = trace_reduce.reduce(ctx["record"])
    line = report(ctx, lambda name: cell.reader(name)(ctx),
                  session_times(trace_reduce.find_xplane(keep)))
    line["correct"] = result["correct"]
    print("clock_check " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
