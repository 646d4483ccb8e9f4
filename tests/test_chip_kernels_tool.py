"""tools/chip_kernels.py contract: two JSON lines, numerics rows, ring rows;
the differential timer the perf tools share, and the flag parser they read."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import chip_kernels  # noqa: E402

from ddw_tpu.utils.config import env_flag  # noqa: E402


@pytest.fixture
def full_size(monkeypatch):
    """The constants as a chip run has them, whatever DDW_BENCH_SMOKE said
    when the tool was imported."""
    for name, val in [("SMOKE", False), ("REPEATS", 3),
                      ("MIN_MEASURE_S", 1.0), ("MAX_STEPS", 1024)]:
        monkeypatch.setattr(chip_kernels, name, val)


def _fake_runner(latency: float, step: float, chunk: int = 1):
    """``run_n`` on a clock that only the runner advances: every call costs a
    fixed ``latency`` (dispatch + fetch) plus ``step`` per step."""
    calls = []

    def run_n(n):
        calls.append(n)
        return latency + n * step

    if chunk > 1:
        run_n.chunk = chunk
    return run_n, calls


@pytest.mark.parametrize("latency", [0.0, 0.5, 8.0])
def test_time_steps_differential_cancels_fixed_latency(full_size, latency):
    run_n, calls = _fake_runner(latency, step=0.25)
    dt, n = chip_kernels._time_steps(run_n)
    # 8 steps of 0.25 s already hold MIN_MEASURE_S: no doubling, and the
    # per-step time is the step's own whatever each call's fixed cost
    assert (dt, n) == (2.0, 8)
    assert calls == [16, 8] * chip_kernels.REPEATS


@pytest.mark.parametrize("step,want_n", [
    (2.0 ** -7, 128),       # 8 -> 128 before T(2N) - T(N) holds 1 s
    (2.0 ** -20, 1024),     # never holds it: stops at MAX_STEPS
])
def test_time_steps_doubles_until_minimum_or_max_steps(full_size, step,
                                                       want_n):
    run_n, calls = _fake_runner(0.5, step)
    dt, n = chip_kernels._time_steps(run_n)
    assert (dt, n) == (want_n * step, want_n)
    sizes = [8 * 2 ** i for i in range(want_n.bit_length() - 3)]  # 8..want_n
    assert calls == ([c for m in sizes for c in (2 * m, m)]
                     + [2 * n, n] * (chip_kernels.REPEATS - 1))
    assert (dt >= chip_kernels.MIN_MEASURE_S) == (n < chip_kernels.MAX_STEPS)


def test_time_steps_runner_with_chunk_gets_multiples_of_it(full_size):
    run_n, calls = _fake_runner(0.5, 2.0 ** -5, chunk=3)
    dt, n = chip_kernels._time_steps(run_n)
    assert n == 36 and dt == n * 2.0 ** -5      # 9 -> 18 -> 36
    assert all(c % 3 == 0 for c in calls)


def test_time_steps_without_a_positive_differential_times_one_run(full_size):
    run_n, calls = _fake_runner(0.5, 0.0)       # the clock cannot tell N apart
    dt, n = chip_kernels._time_steps(run_n)
    assert (dt, n) == (0.5, chip_kernels.MAX_STEPS)
    assert calls[-1] == n


@pytest.mark.parametrize("raw,want", [
    (None, False), ("", False), ("0", False), ("false", False), ("no", False),
    ("off", False), (" OFF ", False),
    ("1", True), ("true", True), ("yes", True), ("on", True), (" True ", True),
    ("2", ValueError), ("ture", ValueError), ("enable", ValueError),
])
def test_env_flag_accepts_both_spellings_and_refuses_the_rest(
        monkeypatch, raw, want):
    name = "DDW_TEST_ENV_FLAG"
    if raw is None:
        monkeypatch.delenv(name, raising=False)
    else:
        monkeypatch.setenv(name, raw)
    if want is ValueError:
        # a typo must refuse, naming the variable, not flip the flag
        with pytest.raises(ValueError, match=name):
            env_flag(name)
    else:
        assert env_flag(name) is want


# tool smoke (~10 s of interpreter work) — tier-2 with the other tool smokes;
# the kernels' own numerics stay in tier-1 (test_depthwise, test_collectives)
@pytest.mark.slow
def test_chip_kernels_smoke():
    env = dict(os.environ, DDW_BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/chip_kernels.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    dw, ring = (json.loads(l) for l in out.stdout.strip().splitlines()[-2:])
    assert dw["mode"] == "interpret"  # CPU run covers plumbing, not Mosaic
    assert all(r["numerics_ok"] for r in dw["depthwise"])
    assert "fwd_ms" not in dw["depthwise"][0]  # the interpreter is not timed
    # 8 virtual devices: the ring runs at n=2 and n=8 and matches psum
    assert set(ring["ring"]) == {"n2", "n8"}
    assert all(r["numerics_ok"] for r in ring["ring"].values())
