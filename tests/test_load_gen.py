"""tools/load_gen.py contract: one JSON line; the fleet-scaling pin —
2-replica closed-loop goodput strictly above 1 replica at saturating
concurrency (the ReplicaSet acceptance number, measured through the real
HTTP path end to end)."""

import json
import os
import subprocess
import sys

import pytest

# self-hosted gateway sweep at hidden 384 — tier-2 wall clock
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    return dict(os.environ, DDW_BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=8",
                PYTHONPATH=REPO)


def test_load_gen_smoke_two_replicas_beat_one():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/load_gen.py")],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    csingle, cdual = d["closed"]["single"], d["closed"]["dual"]
    for row in (csingle, cdual):
        assert row["mode"] == "closed" and row["completed"] == 32
        assert row["goodput_rps"] > 0 and row["tokens_per_sec"] > 0
        assert row["p99_ms"] >= row["p95_ms"] >= row["p50_ms"] > 0
        assert sum(row["errors"].values()) == 0
    assert cdual["replicas"] == 2 and csingle["replicas"] == 1
    # THE pin: at saturating burst load under an SLO deadline, the
    # 2-replica fleet's goodput is strictly above the single replica's —
    # double the slot capacity means the whole burst admits at t=0 with
    # zero queue wait, while the single replica's second wave waits a
    # full wave and cannot make the sub-wave deadline (shed requests
    # cost no device time)
    bsingle, bdual = d["burst"]["single"], d["burst"]["dual"]
    assert d["burst"]["deadline_ms"] > 0
    for row in (bsingle, bdual):
        assert row["mode"] == "open" and row["offered"] == 8
        assert row["completed"] + row["shed"] == 8
    assert bdual["completed"] > bsingle["completed"], (bsingle, bdual)
    assert bdual["slo_attainment"] > bsingle["slo_attainment"]
    # the single fleet really was SLO-starved, and its sheds were
    # deadline sheds (504), not queue-full refusals
    assert bsingle["shed"] >= 1 and bsingle["errors"]["504"] >= 1
    assert bdual["slo_attainment"] >= 0.75


def test_load_gen_chaos_kill_one_replica_mid_run():
    """The chaos-arm pin (tier-2; tests/test_fleet_supervision.py carries
    the tier-1 representative): with DDW_FAULT=serve:crash killing one of
    two replicas mid-run, fleet goodput stays above zero, every request
    resolves (200 or a structured refusal the client's backoff reported),
    the supervisor restarts the replica within budget, and it is serving
    again — circuit closed, generation bumped — by the end of the run."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/load_gen.py"),
         "--chaos"],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])["chaos"]
    row = d["row"]
    # goodput through the death: the fleet kept completing requests
    assert row["completed"] >= 1 and row["goodput_rps"] > 0
    # every request resolved: completions + surfaced refusals == offered
    assert row["completed"] + sum(row["errors"].values()) == row["offered"]
    # the kill really happened, was contained, and was recovered from
    assert d["replica_failures"] >= 1.0
    assert d["restarts"][0] >= 1
    assert d["replica_states"] == ["alive", "alive"]
    assert d["generations"][0] >= 1
    assert d["circuits"][1] == "closed"


def test_load_gen_deploy_arm_zero_downtime_rollout():
    """The deploy-arm pin (tier-2; tests/test_deploy.py carries the
    tier-1 representative): a rolling weight hot-swap across a 2-process
    fleet under closed-loop load completes with goodput > 0 mid-rollout,
    zero failed requests, and every replica on the new digest."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/load_gen.py"),
         "--deploy"],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])["deploy"]
    assert d["completed_during_rollout"] > 0 and d["failed"] == 0
    assert d["rollout_s"] > 0
    dv = d["deploy"]
    assert dv["status"] == "done" and dv["fleet_generation"] == 1
    assert dv["checkpoints"] == [d["digest_b"]] * 2
    assert dv["steps"] == [[0, "recycled"], [1, "recycled"]]
    assert d["digest_a"] != d["digest_b"]


def test_load_gen_fleet_prefix_arm_warm_across_recycle():
    """The fleet prefix-cache pin (tier-2; tests/test_fleet_prefix.py
    carries the tier-1 representatives): over the real HTTP path a
    2-replica fleet on a shared-prefix workload shows cross-replica cache
    hits in /stats (the index fed through the routing path, with
    routed_cache_hit counting the router using it), and a recycle fired
    while phase-B clients are live rejoins replica 0 warm via the
    supervisor's top-K prefix replay — hit tokens keep growing and a
    pinned greedy probe answers bit-identically across the restart."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/load_gen.py"),
         "--fleet-prefix"],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])["fleet_prefix"]
    for row in (d["phase_a"], d["phase_b"]):
        assert row["completed"] == 24
        assert sum(row["errors"].values()) == 0
    assert d["hit_tokens_a"] > 0
    assert d["routed_cache_hit"] > 0
    assert d["prefix_index"]["keys"] >= 1
    # the shared head is the hot key, and after the drill BOTH replicas
    # hold it (replica 0 re-learned it from the warm replay)
    assert d["recycled"] and d["recycle"]["action"] == "drained_restarted"
    assert d["recycle"]["readmit"] == "probed_closed"
    assert d["warm_replays"] > 0
    assert d["replica_cache_keys"][0] > 0
    assert d["hit_tokens_b"] > d["hit_tokens_a"]
    assert d["identity_preserved"] is True


def test_load_gen_trace_arm_covers_every_request_once(tmp_path):
    """The tracing acceptance pin (tier-2; tests/test_trace.py and
    tests/test_deploy.py carry the tier-1 representatives): one command
    against a 2-process fleet produces a single Perfetto-loadable JSON in
    which EVERY completed request is covered by exactly one trace, a
    sampled request shows causally-linked spans across gateway routing,
    child admit/prefill and >= 2 decode ticks, and nothing was dropped
    from any ring. The arm's own DDW_BENCH_SMOKE assertions enforce the
    linkage; this test pins the wire contract on top."""
    trace_out = str(tmp_path / "fleet_trace.json")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/load_gen.py"),
         "--trace", "--trace-out", trace_out],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])["trace"]
    assert d["completed"] == 12
    assert d["traced"] == 12 and d["unique"] == 12
    assert d["covered_once"] == [1] * 12        # each request, exactly once
    assert d["sampled"]["linked"] is True       # parent POINTERS, not names
    assert d["sampled"]["ticks"] >= 2
    assert d["sampled"]["replica"].startswith("replica")
    assert d["dropped"] == 0
    # the Perfetto file really landed and is self-describing
    with open(trace_out) as f:
        ch = json.load(f)
    names = {e["args"]["name"] for e in ch["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert "gateway" in names
    assert any(n.startswith("replica") for n in names)


def test_load_gen_tenants_arm_attributes_noisy_sheds():
    """The multi-tenant QoS pin (tier-2; tests/test_adapters.py carries
    the tier-1 unit/identity representatives): skewed adapter traffic
    from two quiet tenants plus a quota-saturating noisy one — quiet
    tenants complete everything with zero sheds, every 429 names the
    noisy tenant, and the gateway's live per-tenant /stats counters
    equal the clients' own offline ledger exactly. The arm's own
    DDW_BENCH_SMOKE assertions enforce all of that; this test pins the
    wire contract on top."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/load_gen.py"),
         "--tenants"],
        capture_output=True, text=True, env=_env(), cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])["tenants"]
    assert d["errors"] == []
    assert d["ledger"]["acme"]["shed"] == 0
    assert d["ledger"]["beta"]["shed"] == 0
    assert d["ledger"]["noisy"]["shed"] >= 1
    assert d["sheds_attributed"] == d["ledger"]["noisy"]["shed"]
    for t, row in d["ledger"].items():
        assert d["live"][t]["ok"] == row["ok"], t
        assert d["live"][t]["shed"] == row["shed"], t
    assert d["adapter_loads"] == 2.0
    assert d["adapters_resident"] == ["fin", "legal"]


def test_load_gen_refuses_cpu_fallback():
    env = dict(_env(), DDW_REQUIRE_TPU="1")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/load_gen.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert out.returncode == 4
    assert "refusing" in out.stderr
