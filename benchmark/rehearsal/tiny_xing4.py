#!/usr/bin/env python3
"""End-to-end rehearsal of ``xing4_train_s4096`` at the family's tiny sizes on
the CPU: everything of a run but the look for a chip
(``benchmark/run.py::rehearse``), the program against the plain reference on
the timed path's first two steps — latent attention at unequal head widths
with YaRN's turn, the hyper-connected streams with their Sinkhorn rounds, the
leading dense layer and the routed and shared SwiGLU experts, the correction
bias moving between the two steps, the multi-token-prediction term — with the
layers' counters among the checks. Reports what was compared and counted,
never a device metric. Run by hand before a chip call:

    python3 benchmark/rehearsal/tiny_xing4.py [--trace]
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run                        # noqa: E402
from benchmark.families.lm_latent_hc_moe_train import TINY    # noqa: E402
from benchmark.tests import tiny                              # noqa: E402

CELL = "xing4_train_s4096"
# the benchmark's own table of tiny sizes, for whoever reads it after this
tiny.TINY.setdefault("lm_latent_hc_moe_train", TINY)


def main(argv) -> int:
    out = bench_run.rehearse(CELL, 2 ** 31 + 11, 1.0, "--trace" in argv, TINY)
    out.pop("record")
    out["window"].pop("epoch_s")
    print(f"rehearsal {CELL}: " + json.dumps(out), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
