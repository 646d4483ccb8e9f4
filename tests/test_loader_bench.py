"""tools/loader_bench.py contract + regression floors for the host pipeline.

The loader's "the TPU never waits on host IO" claim needs a number on the
host side; this pins the tool's output shape and very conservative records/s
floors so a regression that craters a fast path (e.g. an accidental
per-record decode on raw_u8) fails CI even on the loaded 1-core host.
"""

import pytest
import json
import os
import subprocess
import sys

# loader throughput bench — beyond the tier-1 wall-clock budget
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Floors are ~100x below the rates measured on the 1-core CI host in smoke
# shapes (32x32, batch 8): raw_u8 ~83k, feature ~145k, token ~939k, jpeg
# ~19k rec/s. They only catch order-of-magnitude regressions — by design;
# this host is shared and slow.
FLOORS = {"jpeg": 150, "raw_u8": 800, "raw_u8_assemble": 2000,
          "feature": 1500, "token": 8000}


def test_loader_bench_smoke_and_floors(tmp_path):
    env = dict(os.environ, DDW_BENCH_SMOKE="1", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=REPO, TMPDIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/loader_bench.py"),
         "--steps", "8"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(d["paths"]) == {"jpeg", "raw_u8", "raw_u8_assemble",
                               "feature", "token"}
    for name, row in d["paths"].items():
        assert row["records_per_sec"] > FLOORS[name], (name, row)
        assert row["steps"] > 0
        # floors are a 1-worker contract (0 = the workerless assemble loop)
        assert row["workers"] == (0 if name == "raw_u8_assemble" else 1)
    # materialized paths must beat live decode per record
    assert (d["paths"]["raw_u8"]["records_per_sec"]
            > d["paths"]["jpeg"]["records_per_sec"])
    # the uint8 assemble ceiling (training path: dequant rides the device)
    # must beat the host-dequant row — the gap IS the dequant cost
    assert (d["paths"]["raw_u8_assemble"]["records_per_sec"]
            > d["paths"]["raw_u8"]["records_per_sec"])
