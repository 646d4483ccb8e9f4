"""tools/step_trace.py contract: traces land on disk, JSON line reports them.

A typo'd config name must fail in CI, not on the chip.
"""

import pytest
import json
import os
import subprocess
import sys

# profiler-trace tool smoke — beyond the tier-1 wall-clock budget
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_step_trace_smoke(tmp_path):
    env = dict(os.environ, DDW_BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/step_trace.py"),
         "vit", "lm_flash", "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    for name in ("vit", "lm_flash"):
        assert d[name]["steps"] > 0 and d[name]["seconds"] > 0
        assert os.listdir(d[name]["dir"])  # profiler wrote something

    bad = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/step_trace.py"), "nope"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert bad.returncode != 0 and "unknown configs" in bad.stderr

    # the offline decomposition pass reads the capture back
    summ = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/trace_summary.py"),
         d["lm_flash"]["dir"], "--top", "5"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert summ.returncode == 0, summ.stderr[-2000:]
    s = json.loads(summ.stdout.strip().splitlines()[-1])
    assert s["processes"], s
    proc = next(iter(s["processes"].values()))
    assert proc["busy_ms"] > 0 and proc["top_ops"]
    assert abs(sum(proc["buckets_pct"].values()) - 100) < 1

    missing = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/trace_summary.py"),
         str(tmp_path / "empty")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert missing.returncode != 0 and "trace.json.gz" in missing.stderr


def test_trace_summary_filters_to_op_lane(tmp_path):
    """TPU Chrome traces nest lanes under the device pid ('XLA Modules' /
    'Steps' spans ENCLOSE the 'XLA Ops' events): only the op lane may be
    aggregated, or busy_ms double-counts past wall time. And pids are only
    unique per trace FILE — one file's op-lane filter must not drop another
    file's events for the same pid (multi-host captures reuse pids)."""
    import gzip

    def write(name, events):
        p = tmp_path / name
        p.write_bytes(gzip.compress(json.dumps(
            {"traceEvents": events}).encode()))

    meta = lambda pid, tid, kind, nm: {
        "ph": "M", "pid": pid, "tid": tid, "name": kind, "args": {"name": nm}}
    ev = lambda pid, tid, nm, dur: {
        "ph": "X", "pid": pid, "tid": tid, "name": nm, "ts": 0, "dur": dur}

    write("a.trace.json.gz", [
        meta(1, 0, "process_name", "/device:TPU:0"),
        meta(1, 10, "thread_name", "XLA Modules"),
        meta(1, 11, "thread_name", "XLA Ops"),
        meta(1, 12, "thread_name", "Steps"),
        ev(1, 10, "jit_step", 100_000),          # enclosing module span
        ev(1, 12, "train_step 3", 100_000),      # enclosing step span
        ev(1, 11, "fusion.1", 40_000),
        ev(1, 11, "dot_general.2", 30_000),
    ])
    # same pid, different file: a host process with no op lane — all kept
    write("b.trace.json.gz", [
        meta(1, 0, "process_name", "host python"),
        meta(1, 7, "thread_name", "python"),
        ev(1, 7, "np.copy", 50_000),
    ])
    # a SECOND host's device with the same display name: must stay a
    # separate entry, not be summed into file a's device
    write("c.trace.json.gz", [
        meta(1, 0, "process_name", "/device:TPU:0"),
        meta(1, 11, "thread_name", "XLA Ops"),
        ev(1, 11, "fusion.9", 20_000),
    ])

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/trace_summary.py"),
         str(tmp_path)], capture_output=True, text=True, cwd=REPO,
        timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    s = json.loads(out.stdout.strip().splitlines()[-1])
    dev = s["processes"]["/device:TPU:0 [file0]"]
    assert dev["busy_ms"] == 70.0, dev  # 40+30 ms, enclosing spans excluded
    assert dev["lanes"] == ["XLA Ops"]
    assert {r["op"] for r in dev["top_ops"]} == {"fusion.1", "dot_general.2"}
    host = s["processes"]["host python"]
    assert host["busy_ms"] == 50.0, host  # file A's filter must not leak in
    dev2 = s["processes"]["/device:TPU:0 [file2]"]
    assert dev2["busy_ms"] == 20.0, dev2
