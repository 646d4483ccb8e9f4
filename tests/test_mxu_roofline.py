"""tools/mxu_roofline.py: dot_general parsing + tile-quantization math.

The parser is pure text analysis — pin it on crafted StableHLO lines (with
and without batching_dims, multi-dim contractions) where the right MAC and
padded-MAC counts are hand-checkable; then one smoke lowering proves the
end-to-end path against the real LM step.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from mxu_roofline import analyze, dot_rows  # noqa: E402

SNIPPET = """
    %3 = stablehlo.dot_general %1, %2, contracting_dims = [1] x [0], precision = [DEFAULT, DEFAULT] : (tensor<64x192xbf16>, tensor<192x768xbf16>) -> tensor<64x768xf32>
    %9 = stablehlo.dot_general %7, %8, batching_dims = [0, 1] x [0, 1], contracting_dims = [3] x [3], precision = [DEFAULT, DEFAULT] : (tensor<4x2x64x48xbf16>, tensor<4x2x64x48xbf16>) -> tensor<4x2x64x64xf32>
"""


def test_dot_rows_parses_both_forms():
    rows = dot_rows(SNIPPET)
    assert len(rows) == 2
    proj, attn = rows
    # [64,192]x[192,768]: B=1 M=64 N=768 K=192
    assert (proj["B"], proj["M"], proj["N"], proj["K"]) == (1, 64, 768, 192)
    assert proj["macs"] == 64 * 768 * 192
    # padded: M 64->64 (8q), N 768->768, K 192->256
    assert proj["padded_macs"] == 64 * 768 * 256
    assert abs(proj["util"] - 192 / 256) < 1e-9
    # batched attention dot: B=8, M=64, N=64, K=48
    assert (attn["B"], attn["M"], attn["N"], attn["K"]) == (8, 64, 64, 48)
    assert attn["padded_macs"] == 8 * 64 * 128 * 128  # N,K both pad to 128

    a = analyze(SNIPPET)
    assert a["n_dots"] == 2
    assert a["macs"] == proj["macs"] + attn["macs"]
    assert 0 < a["mxu_util"] < 1
    assert len(a["top_shapes"]) == 2


@pytest.mark.slow  # tier-1 budget (PR 16): the dot-row parser keeps its
#                    tier-1 unit above; this end-to-end LM smoke rides
#                    tier-2 with the bench arms it instruments
def test_smoke_end_to_end_lm():
    env = dict(os.environ, DDW_BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/mxu_roofline.py"),
         "--configs", "lm_flash"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])["configs"]["lm_flash"]
    assert d["n_dots"] > 0 and 0 < d["mxu_util"] <= 1
    # smoke lm: hidden 64 -> every projection K=64 pads to 128; util must
    # reflect real padding, not default to 1
    assert d["mxu_util"] < 0.9
