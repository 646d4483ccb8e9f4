"""tools/attn_dispatch_evidence.py: structural remat evidence, no chip.

Smoke shapes exercise the mechanism: per-arm lowering, tier report, the
[B,H]-batched attention-dot count, and the ckpt-vs-plain structural delta
(a checkpointed attention must carry exactly 2 extra attention dots per
layer — the recomputed QKᵀ and PV forwards inside the backward).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow   # subprocess bench smoke — the ROADMAP's "benches"
#                     tier-2 class
def test_smoke_arms_and_remat_delta():
    env = dict(os.environ, DDW_BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/attn_dispatch_evidence.py"),
         "--configs", "lm_flash", "--arms", "default,ckpt_force"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    rows = d["configs"]["lm_flash"]
    base, ckpt = rows["default"], rows["ckpt_force"]
    assert "error" not in base and "error" not in ckpt, rows
    # smoke shapes are tiny -> default is the plain tier
    assert base["tier"] == "xla" and ckpt["tier"] == "xla_ckpt"
    depth = 2  # smoke lm config
    # plain: 6 attention dots per layer (2 fwd + 4 bwd)
    assert base["attn_dot_general"] == 6 * depth, base
    # checkpointed backward recomputes the 2 forward dots per layer
    assert ckpt["attn_dot_general"] == base["attn_dot_general"] + 2 * depth
    assert ckpt["dot_general"] > base["dot_general"]
    assert ckpt["no_op_vs_default"] is False
