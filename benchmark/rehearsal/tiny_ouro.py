#!/usr/bin/env python3
"""End-to-end rehearsal of ``ouro_train_s8192`` at the family's tiny sizes on
the CPU: everything of a run but the look for a chip
(``benchmark/run.py::rehearse``), the program against the plain reference on
the timed path's first two steps — sandwich-normed blocks run four times over
one set of weights, the four exits through one head, the gate and the
expected loss with its entropy term — with the exits' counters among the
checks. Reports what was compared and counted, never a device metric. Run by
hand before a chip call:

    python3 benchmark/rehearsal/tiny_ouro.py [--trace] [--controls fp8] [--faults]

``--controls fp8`` also follows the two steps with the reference's products
rounded to float8 and says which of the cell's limits that control fails at
the tiny sizes (exit 1 where it fails none); ``--faults`` runs the rehearsal
once with each of ``tools/ouro_faults.py``'s three planted (exit 1 where
``correct`` lets one through).
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run                        # noqa: E402
from benchmark.families.lm_looped_train import TINY           # noqa: E402
from benchmark.harness import check                           # noqa: E402
from benchmark.harness.manifest import Cell, load_manifest    # noqa: E402
from benchmark.tests import tiny                              # noqa: E402
from benchmark.tools import ouro_faults                       # noqa: E402

CELL = "ouro_train_s8192"
# the benchmark's own table of tiny sizes, for whoever reads it after this
tiny.TINY.setdefault("lm_looped_train", TINY)


def main(argv) -> int:
    if "--controls" in argv:
        return control(argv[argv.index("--controls") + 1])
    if "--faults" in argv:
        let_through = 0
        for fault in ouro_faults.FAULTS:
            with ouro_faults.planted(fault):
                let_through += ouro_faults.tiny(fault)
        return 1 if let_through else 0
    out = bench_run.rehearse(CELL, 2 ** 31 + 11, 1.0, "--trace" in argv, TINY)
    out.pop("record")
    out["window"].pop("epoch_s")
    print(f"rehearsal {CELL}: " + json.dumps(out), flush=True)
    return 0 if out["correct"] else 1


def control(precision: str) -> int:
    """The same run with the reference followed once more in ``precision``:
    the control's numbers held to the cell's limits."""
    import jax

    cell = Cell(load_manifest(), CELL)
    result = cell.family.run(cell, 2 ** 31 + 11, 1.0, False, time.time(),
                             jax.devices()[:1], None, tiny=TINY,
                             controls=(precision,))
    numbers = result["controls"][precision]
    failed = [name for name, value in numbers.items()
              if name in cell.limits
              and not check.judge({name: value}, cell.limits)]
    print(f"rehearsal {CELL}: the {precision} control at the tiny sizes "
          f"reads {json.dumps(numbers)}; fails {failed}", flush=True)
    return 0 if failed and result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
