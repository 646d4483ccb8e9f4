#!/usr/bin/env python3
"""The planted fault of ``nemotron3nano_train_s8192``: the program with the
state-space layers' carried state DROPPED between chunks (each chunk starts
from zero, ``ddw_tpu/ops/ssd.py::carry_states`` put out of action), through
``sweep_first_steps.py``'s own run of the cell. A chunk's own products are
all still there, so the program trains and nothing looks broken; the
``correct`` comparison against the recurrence has to fail it, and this prints
by which limits (PERF.md section 2). Same arguments as
``sweep_first_steps.py``.

    python3 benchmark/tools/carry_fault.py --workload nemotron3nano_train_s8192 \\
        --seeds 11 --steps-per-epoch 2 --out chiprun_out/carry_fault.jsonl
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import sweep_first_steps                 # noqa: E402


def main() -> int:
    import jax.numpy as jnp

    from ddw_tpu.ops import ssd

    ssd.carry_states = lambda local, decay: jnp.zeros_like(local)
    print("carry_fault: every chunk of the scan starts from a zero state",
          flush=True)
    return sweep_first_steps.main()


if __name__ == "__main__":
    sys.exit(main())
