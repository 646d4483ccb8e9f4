"""Summarize a jax.profiler trace into a per-op time table, offline.

``tools/step_trace.py`` captures traces on the chip; this tool decomposes
them afterwards, anywhere — no tensorboard required, just the Chrome-trace
JSON the profiler always writes
(``plugins/profile/<run>/*.trace.json.gz``). For each process (device) it
aggregates complete events by op name, buckets them into families
(matmul/fusion/conv/collective/copy/infeed), and prints the top ops with
their share of that process's busy time — the "where do the 84% of missing
MFU go" table for the transformer gap (ROADMAP S3/S4).

Usage: ``python tools/trace_summary.py benchruns/traces/lm_flash [--top 20]``
Prints ONE JSON line; the human-readable table goes to stderr.
"""

import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import argparse
import collections
import glob
import gzip
import json

_BUCKETS = (
    ("collective", ("all-reduce", "all-gather", "reduce-scatter",
                    "collective", "all-to-all", "ppermute")),
    ("matmul", ("dot", "gemm", "matmul", "convolution")),
    ("fusion", ("fusion",)),
    ("copy", ("copy", "bitcast", "transpose", "reshape")),
    ("infeed", ("infeed", "outfeed", "transfer")),
)


def bucket_of(name: str) -> str:
    low = name.lower()
    for bucket, keys in _BUCKETS:
        if any(k in low for k in keys):
            return bucket
    return "other"


def load_events(trace_dir: str):
    pats = [os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz"),
            os.path.join(trace_dir, "*.trace.json.gz")]
    paths = sorted(p for pat in pats for p in glob.glob(pat))
    if not paths:
        raise SystemExit(f"no *.trace.json.gz under {trace_dir} — pass the "
                         f"directory given to jax.profiler.trace")
    # pids are only unique WITHIN one trace file — a multi-host capture (one
    # file per host) reuses them. Key everything by (file_idx, pid) so one
    # file's op-lane filter can never drop another file's events.
    events, procs, threads = [], {}, {}
    for fi, p in enumerate(paths):
        d = json.loads(gzip.open(p).read())
        for e in d.get("traceEvents", []):
            if e.get("ph") == "M" and e.get("name") == "process_name":
                procs[(fi, e["pid"])] = e["args"]["name"]
            elif e.get("ph") == "M" and e.get("name") == "thread_name":
                threads[(fi, e["pid"], e.get("tid"))] = e["args"]["name"]
            elif e.get("ph") == "X" and e.get("dur", 0) > 0:
                e["_fpid"] = (fi, e["pid"])
                events.append(e)
    return events, procs, threads


def summarize(trace_dir: str, top: int) -> dict:
    events, procs, threads = load_events(trace_dir)
    # jax.profiler's Chrome export nests lanes under each device pid: "XLA
    # Modules" / "Steps" spans ENCLOSE the per-op "XLA Ops" events, so summing
    # every 'X' event under a pid double-counts — busy_ms can exceed wall
    # time. Keep only the op-level lane(s) where one exists; pids without a
    # recognizable op lane (host threads, CPU captures) keep all lanes.
    op_tids: dict = collections.defaultdict(set)
    for (fi, pid, tid), name in threads.items():
        if "xla ops" in name.lower():
            op_tids[(fi, pid)].add(tid)
    # Display names collide across files too (every host calls its device
    # "/device:TPU:0") — merging them would sum distinct devices' busy time
    # into one entry. Suffix the file index only when a name is ambiguous.
    name_files: dict = collections.defaultdict(set)
    for (fi, pid), name in procs.items():
        name_files[name].add(fi)

    def display(fpid):
        name = procs.get(fpid, str(fpid))
        if len(name_files.get(name, ())) > 1:
            return f"{name} [file{fpid[0]}]"
        return name

    per_proc: dict = collections.defaultdict(lambda: collections.Counter())
    counts: dict = collections.defaultdict(lambda: collections.Counter())
    lanes_used: dict = collections.defaultdict(set)
    for e in events:
        fpid = e["_fpid"]
        if op_tids.get(fpid) and e.get("tid") not in op_tids[fpid]:
            continue
        key = display(fpid)
        per_proc[key][e["name"]] += e["dur"]
        counts[key][e["name"]] += 1
        lanes_used[key].add(
            threads.get((*fpid, e.get("tid")), str(e.get("tid"))))

    out = {"trace_dir": trace_dir, "processes": {}}
    # Device processes first (the interesting ones on a TPU capture).
    ordered = sorted(per_proc, key=lambda k: ("TPU" not in k, k))
    for proc in ordered:
        ops = per_proc[proc]
        total = sum(ops.values())
        buckets = collections.Counter()
        for name, dur in ops.items():
            buckets[bucket_of(name)] += dur
        rows = [{"op": name, "total_ms": round(dur / 1e3, 3),
                 "calls": counts[proc][name],
                 "pct": round(100 * dur / total, 2),
                 "bucket": bucket_of(name)}
                for name, dur in ops.most_common(top)]
        out["processes"][proc] = {
            "busy_ms": round(total / 1e3, 3),
            "lanes": sorted(lanes_used[proc]),
            "buckets_pct": {b: round(100 * d / total, 2)
                            for b, d in buckets.most_common()},
            "top_ops": rows,
        }
        print(f"-- {proc}: {total / 1e3:.1f} ms busy --", file=sys.stderr)
        for b, d in buckets.most_common():
            print(f"   {b:<11} {100 * d / total:5.1f}%", file=sys.stderr)
        for r in rows[:top]:
            print(f"   {r['pct']:5.1f}%  {r['total_ms']:>10.2f} ms "
                  f"x{r['calls']:<5} {r['op'][:60]}", file=sys.stderr)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()
    print(json.dumps(summarize(args.trace_dir, args.top)))


if __name__ == "__main__":
    main()
