"""tools/xla_cache_stats.py: mine persistent-cache entries offline.

Builds a real cache entry (tiny jitted matmul compiled with
JAX_COMPILATION_CACHE_DIR pointing at a tmp dir) and checks the miner
reads back compile time + an optimized-HLO instruction mix from it —
the offline-evidence path.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE = """
import jax, jax.numpy as jnp
@jax.jit
def f(x):
    return jnp.tanh(x @ x).sum()
print(f(jnp.ones((256, 256), jnp.float32)))
"""


def test_cache_entry_mined(tmp_path):
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=REPO, JAX_COMPILATION_CACHE_DIR=str(cache),
               # default thresholds skip caching sub-second tiny compiles
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    r = subprocess.run([sys.executable, "-c", _COMPILE], env=env,
                       capture_output=True, text=True, cwd=REPO, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert any(f.endswith("-cache") for f in os.listdir(cache))

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/xla_cache_stats.py"),
         str(cache), "--hlo-out", str(tmp_path / "hlo")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    rows = [e for e in d["entries"] if e["name"].startswith("jit_f")]
    assert rows, d["entries"]
    e = rows[0]
    assert e["method"] == "hlo"
    assert e["n_instructions"] > 0
    assert e["families"].get("dot", 0) >= 1  # the matmul survived to HLO
    assert "compile_s" in e
    assert os.path.exists(e["hlo_path"])
    with open(e["hlo_path"]) as f:
        assert "HloModule" in f.read(200)

    # empty dir: clean refusal, not a crash
    bad = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/xla_cache_stats.py"),
         str(tmp_path / "nothing")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert bad.returncode != 0 and "no cache entries" in bad.stderr
