#!/usr/bin/env python3
"""End-to-end rehearsal of ``nemotron3nano_train_s8192`` at the family's tiny
sizes on the CPU: everything of a run but the look for a chip
(``benchmark/run.py::rehearse``), the program against the plain reference on
the timed path's first two steps — the chunked scan against the recurrence,
the routed and shared experts, the correction bias moving between the two
steps — with the layers' counters among the checks. Reports what was compared
and counted, never a device metric. Run by hand before a chip call:

    python3 benchmark/rehearsal/tiny_nemotron3nano.py [--trace]
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run                        # noqa: E402
from benchmark.families.lm_hybrid_ssm_moe_train import TINY   # noqa: E402

CELL = "nemotron3nano_train_s8192"


def main(argv) -> int:
    out = bench_run.rehearse(CELL, 2 ** 31 + 11, 1.0, "--trace" in argv, TINY)
    out.pop("record")
    out["window"].pop("epoch_s")
    print(f"rehearsal {CELL}: " + json.dumps(out), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
