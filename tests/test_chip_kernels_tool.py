"""tools/chip_kernels.py contract: two JSON lines, numerics rows, ring rows."""

import json
import os
import subprocess
import sys

import pytest

# tool smoke (~10 s of interpreter work) — tier-2 with the other tool smokes;
# the kernels' own numerics stay in tier-1 (test_depthwise, test_collectives)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
