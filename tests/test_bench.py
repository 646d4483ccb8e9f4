"""bench.py contract: one JSON line, full matrix, MFU fields present.

Runs the benchmark in DDW_BENCH_SMOKE mode (tiny shapes, 2 measured steps) on
whatever backend the test session uses — the assertions check structure and
positivity, not absolute performance.
"""

import json
import os
import subprocess
import sys

import pytest

# bench arms run full training sweeps — beyond the tier-1 wall-clock budget
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_proc(**extra_env):
    """Smoke-run bench.py on the virtual 8-device CPU backend (same
    environment as the root conftest)."""
    env = dict(os.environ, DDW_BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               **extra_env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)


def _run_bench(**extra_env):
    """A passing smoke run, parsed from its one-line JSON."""
    out = _bench_proc(**extra_env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def bench_json():
    return _run_bench()


def test_headline_contract(bench_json):
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in bench_json
    assert bench_json["value"] > 0
    assert bench_json["unit"] == "images/sec/chip"


def test_matrix_rows(bench_json):
    configs = bench_json["configs"]
    for name in ("mobilenet_v2_frozen", "mobilenet_v2_frozen_feature_cache",
                 "mobilenet_v2_unfrozen", "resnet50",
                 "vit", "lm_flash", "lm_moe",
                 "e2e_raw_u8", "e2e_feature_cache"):
        row = configs[name]
        assert "error" not in row, f"{name}: {row}"
        assert row["rate_per_chip"] > 0
        assert row["step_time_ms"] > 0
        assert row["step_flops"] > 0  # XLA's cost analysis answered
        assert row["achieved_tflops_per_chip"] > 0
    assert configs["lm_flash"]["unit"] == "tokens/sec/chip"
    # e2e rows measure the loader-fed system: always host-loop, and they
    # must say what fed them (encoding + table size, for the honest caveat).
    for name in ("e2e_raw_u8", "e2e_feature_cache"):
        row = configs[name]
        assert row["chain"] == "loop"
        assert row["pipeline"] == "loader_prefetch"
        assert row["table_records"] > 0


def test_flops_ordering(bench_json):
    """Unfrozen backward must cost more FLOPs than frozen (backbone skipped),
    and the cached-feature head step must cost far less than either (the whole
    backbone forward is gone)."""
    c = bench_json["configs"]
    fro = c["mobilenet_v2_frozen"]["step_flops"]
    unf = c["mobilenet_v2_unfrozen"]["step_flops"]
    head = c["mobilenet_v2_frozen_feature_cache"]["step_flops"]
    assert unf > fro * 1.5
    assert head < fro / 50


def test_host_pipeline(bench_json):
    host = bench_json["host_pipeline"]
    assert host["pil_images_per_sec"] > 0
    assert host["native_images_per_sec"] > 0
    assert host["native_ok_fraction"] == 1.0


def test_failing_row_prints_matrix_and_exits_nonzero():
    """A config that raises becomes an ``error`` row; the one JSON line is
    still printed (the other rows survive) and the exit status is 1 — never
    a result that looks like a pass."""
    out = _bench_proc(DDW_BENCH_DW="cudnn",  # bench_vision refuses the knob
                      DDW_BENCH_ONLY=("mobilenet_v2_frozen,"
                                      "mobilenet_v2_frozen_feature_cache"))
    assert out.returncode == 1, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert "DDW_BENCH_DW" in d["configs"]["mobilenet_v2_frozen"]["error"]
    assert d["configs"]["mobilenet_v2_frozen_feature_cache"][
        "rate_per_chip"] > 0
    assert d["error"] == "failed: mobilenet_v2_frozen"
    assert d["value"] is None


def test_full_shapes_refuse_a_cpu():
    """Without DDW_BENCH_SMOKE the benchmark measures a TPU or nothing:
    on the CPU it exits 1 with one JSON line naming the platform."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", DDW_BENCH_ONLY="lm_flash")
    env.pop("DDW_BENCH_SMOKE", None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert out.returncode == 1
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["value"] is None and "'cpu'" in d["error"]


def test_vit_hidden_override_builds_tile_geometry():
    """ModelCfg.hidden=256 + num_heads=2 (the ab_vit_tile geometry) must
    reach the ViT: encoder width, mlp_dim 4x ratio, and head_dim 128 — the
    full-tile shape tools/mxu_roofline.py shows lifts the MFU ceiling from
    59% to 94%."""
    import jax
    import jax.numpy as jnp

    from ddw_tpu.models.registry import build_model
    from ddw_tpu.utils.config import ModelCfg

    model = build_model(ModelCfg(name="vit", num_classes=5, hidden=256,
                                 num_heads=2))
    assert model.hidden == 256
    assert model.mlp_dim == 1024
    assert model.num_heads == 2
    params = model.init({"params": jax.random.PRNGKey(0)},
                        jnp.zeros((1, 32, 32, 3)), train=False)["params"]
    q = params["backbone_block0"]["attn"]["query"]["kernel"]
    assert q.shape == (256, 2, 128)  # (hidden, heads, head_dim=128)


def test_tile_geometry_arm_rows():
    """The ab_lm_tile / ab_vit_tile knobs must produce valid rows tagged
    with the non-default geometry they measured (a silently-default row
    would record the wrong experiment)."""
    d = _run_bench(DDW_BENCH_LM_HEADS="2",
                   DDW_BENCH_VIT_HIDDEN="64", DDW_BENCH_VIT_HEADS="2",
                   DDW_BENCH_ONLY="vit,lm_flash")
    vit, lm = d["configs"]["vit"], d["configs"]["lm_flash"]
    assert vit["rate_per_chip"] > 0
    assert vit["model_shape"] == {"hidden": 64, "num_heads": 2}
    assert lm["rate_per_chip"] > 0
    assert lm["num_heads"] == 2


def test_scan_chained_rows():
    """DDW_BENCH_CHAIN=scan: the lax.scan megastep arm produces valid rows
    tagged "chain": "scan" for vision, feature-cache and LM families — the
    arm must not regress silently in CI."""
    d = _run_bench(
        DDW_BENCH_CHAIN="scan",
        DDW_BENCH_ONLY=("mobilenet_v2_frozen,"
                        "mobilenet_v2_frozen_feature_cache,lm_flash"))
    assert set(d["configs"]) == {"mobilenet_v2_frozen",
                                 "mobilenet_v2_frozen_feature_cache",
                                 "lm_flash"}
    for name, row in d["configs"].items():
        assert row["chain"] == "scan", (name, row)
        assert row["rate_per_chip"] > 0, (name, row)


def test_fused_chain_arm_reports_dispatch_overhead():
    """DDW_BENCH_CHAIN=K (the steps_per_dispatch A/B arm): rows must carry
    the fused-chain tag AND the measured host-loop delta
    (dispatch_overhead_ms_per_step) — the number the amortization claim
    rests on — in smoke mode on CPU, so the arm can't regress silently."""
    d = _run_bench(DDW_BENCH_CHAIN="2",
                   DDW_BENCH_ONLY=("mobilenet_v2_frozen_feature_cache,"
                                   "lm_flash"))
    for name in ("mobilenet_v2_frozen_feature_cache", "lm_flash"):
        row = d["configs"][name]
        assert "error" not in row, (name, row)
        assert row["chain"] == 2 and row["chain_k"] == 2, (name, row)
        assert row["rate_per_chip"] > 0, (name, row)
        assert row["loop_step_time_ms"] > 0, (name, row)
        # the delta is a measurement — sign depends on backend noise; the
        # contract is that it was measured and reported
        assert "dispatch_overhead_ms_per_step" in row, (name, row)


def test_chain_env_validation():
    """A typo'd DDW_BENCH_CHAIN must refuse loudly at import, not silently
    bench the loop arm (same contract as the other knob parsers)."""
    import subprocess

    for bad in ("chain", "1", "-3"):
        out = subprocess.run(
            [sys.executable, "-c", "import bench"],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, DDW_BENCH_CHAIN=bad, JAX_PLATFORMS="cpu"))
        assert out.returncode != 0, bad
        assert "DDW_BENCH_CHAIN" in out.stderr, out.stderr[-500:]
