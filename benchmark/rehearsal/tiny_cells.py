#!/usr/bin/env python3
"""End-to-end rehearsal of each cell at tiny sizes on virtual CPU devices (four,
so that the four-chip cell's mesh, sharding and gradient all-reduce are really
built): everything of a run but the look for a chip. Reports what was compared
and counted, never a device metric. Run by hand before a chip call:

    python3 benchmark/rehearsal/tiny_cells.py [cell ...] [--trace]
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run                        # noqa: E402
from benchmark.harness.manifest import Cell, load_manifest    # noqa: E402
from benchmark.tests.tiny import TINY                         # noqa: E402


def main(argv):
    trace = "--trace" in argv
    names = [a for a in argv if not a.startswith("--")]
    manifest = load_manifest()
    for name in names or [w["name"] for w in manifest["workloads"]]:
        family = Cell(manifest, name).config["family"]
        out = bench_run.rehearse(name, 2 ** 31 + 11, 1.0, trace, TINY[family])
        out.pop("record")
        out["window"].pop("epoch_s")
        print(f"rehearsal {name}: " + json.dumps(out), flush=True)
        if not out["correct"]:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
