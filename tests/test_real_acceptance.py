"""Dry-run of the real-artifact acceptance kit (examples/12_real_acceptance).

Exercises every stage except the two downloads: generated flowers stand in
for tf_flowers, an exported torch-layout state_dict stands in for the
torchvision artifact (the same convert path real ImageNet weights take).
Run 1 proves every stage executes and reports; run 2 records goldens; run 3
proves the whole pipeline reproduces fingerprint-for-fingerprint — the
property a connected machine relies on when it runs this for real.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

# full acceptance-chain dry-run — beyond the tier-1 wall-clock budget
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STAGES = ("environment", "fetch-weights", "fetch-flowers", "convert", "prep",
          "train-single", "train-dist", "hpo", "hpo-dist", "package-score")

# stages --resume may carry forward from a previous run's report (everything
# expensive: downloads, weight convert, the four fits, the packaged scoring)
RESUMABLE = ("fetch-weights", "fetch-flowers", "convert", "train-single",
             "train-dist", "hpo", "hpo-dist", "package-score")


@pytest.fixture(scope="module")
def fixtures_dir(tmp_path_factory):
    """Generated flowers tree + torch-format state_dict fixture."""
    import torch

    from ddw_tpu.data.prep import generate_synthetic_flowers
    from ddw_tpu.models.export import export_torch_mobilenet_v2
    from ddw_tpu.models.registry import build_model
    from ddw_tpu.utils.config import ModelCfg

    root = tmp_path_factory.mktemp("acceptance_fixtures")
    flowers = str(root / "flowers")
    generate_synthetic_flowers(flowers, images_per_class=16, size=48, seed=7)

    import jax

    mcfg = ModelCfg(name="mobilenet_v2", num_classes=5, dropout=0.0,
                    width_mult=0.35, dtype="float32")
    model = build_model(mcfg)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           np.zeros((1, 48, 48, 3), np.float32), train=False)
    sd = export_torch_mobilenet_v2(
        {"params": variables["params"]["backbone"],
         "batch_stats": variables["batch_stats"]["backbone"]})
    wpath = str(root / "mnv2_fixture.pt")
    torch.save({k: torch.from_numpy(np.array(v)) for k, v in sd.items()},
               wpath)
    return {"flowers": flowers, "weights": wpath}


def _run(workdir, fixtures, golden, record=False, expect_fail=False,
         resume=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO)
    cmd = [sys.executable, os.path.join(REPO, "examples/12_real_acceptance.py"),
           "--work", str(workdir), "--quick", "--bar", "0.0",
           "--fixture-weights", fixtures["weights"],
           "--fixture-flowers", fixtures["flowers"],
           "--golden", str(golden)]
    if record:
        cmd.append("--record")
    if resume:
        cmd.append("--resume")
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=1800)
    if expect_fail:
        assert out.returncode != 0, out.stdout[-2000:]
        return out.stdout + out.stderr
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    with open(os.path.join(workdir, "acceptance_report.json")) as f:
        return json.load(f), out.stdout


def test_all_stages_record_and_reproduce(fixtures_dir, tmp_path):
    golden = tmp_path / "golden.json"

    rep1, _ = _run(tmp_path / "run1", fixtures_dir, golden, record=True)
    assert set(rep1) == set(STAGES)
    assert all(rep1[s]["golden"] == "recorded" for s in STAGES)
    assert rep1["prep"]["classes"] == 5
    assert rep1["convert"]["leaves"] > 100  # full backbone tree converted
    assert rep1["environment"]["jax"]  # versions pinned into the golden

    # Same fixtures, fresh workdir, goldens enforced: every deterministic
    # stage must reproduce its fingerprint exactly.
    rep2, _ = _run(tmp_path / "run2", fixtures_dir, golden)
    for s in STAGES:
        assert rep2[s]["golden"] == "match", (s, rep2[s])

    # --resume over a completed workdir: every expensive stage is carried
    # forward from the report (no re-training, no re-download role), and the
    # hpo-dist entry still feeds package-score its tuned params.
    rep3, out3 = _run(tmp_path / "run2", fixtures_dir, golden, resume=True)
    for s in RESUMABLE:
        assert f"[{s}] resumed" in out3, (s, out3[-2000:])
        assert rep3[s]["fingerprint"] == rep2[s]["fingerprint"], s
    assert "tuned_lr" in rep3["hpo-dist"]


def test_golden_mismatch_fails_loudly(fixtures_dir, tmp_path):
    golden = tmp_path / "golden.json"
    golden.write_text(json.dumps(
        {"convert": {"fingerprint": "0" * 64}}))
    out = _run(tmp_path / "run", fixtures_dir, golden, expect_fail=True)
    assert "not reproducing" in out
