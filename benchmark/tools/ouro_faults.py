#!/usr/bin/env python3
"""The planted faults of ``ouro_train_s8192``: the program with one thing
wrong, through ``sweep_first_steps.py``'s own run of the cell. Each trains and
nothing looks broken; the ``correct`` comparison has to fail every one, and
this prints by which limits (PERF.md section 2). ``--fault`` names one:

- ``last_pass_grad_only``: the state stopped between passes
  (``stop_gradient`` on what a pass takes in): a pass's losses reach the
  weights through that pass alone, and the embedding not at all;
- ``norm_not_carried``: the next pass takes the stack's un-normed output where
  it should take the final norm's (the exits still read the normed state);
  planted where a pass's exit is rematerialised (``remat`` full or dots, as
  the cell and the tiny sizes have it);
- ``uniform_exit``: ``p = 1/passes`` for every token whatever the gate says
  (``models/lm.py::exit_distribution``): the gate gets no gradient and the
  exits are weighed evenly.

The other arguments are ``sweep_first_steps.py``'s; ``--tiny`` in their place
runs the cell's rehearsal (the family's tiny sizes, float32, on the CPU: no
device metric) with the fault planted and prints which limits refuse it.

    python3 benchmark/tools/ouro_faults.py --fault norm_not_carried \\
        --workload ouro_train_s8192 --seeds 11 --steps-per-epoch 2
    python3 benchmark/tools/ouro_faults.py --fault uniform_exit --tiny
"""

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

FAULTS = ("last_pass_grad_only", "norm_not_carried", "uniform_exit")
CELL = "ouro_train_s8192"


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` planted for the length of the block. The
    passes' loop is ``models/lm.py::run_passes``'s ``nn.scan`` of one pass,
    ``(state) -> (the final norm's output, the exit's readings)``, whose end
    (the final norm, the gate, the exit's head) is one rematerialised
    function of the stack's output: the first two faults wrap those."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from ddw_tpu.models import lm

    patches = []        # (where, name, wrong)
    if fault == "last_pass_grad_only":
        real_scan = nn.scan

        def scan(fn, **kw):
            if getattr(fn, "__name__", "") != "one_pass":
                return real_scan(fn, **kw)      # an exit's chunks of tokens
            return real_scan(lambda mdl, h, *a: fn(
                mdl, jax.lax.stop_gradient(h), *a), **kw)
        patches.append((nn, "scan", scan))
    elif fault == "norm_not_carried":
        real_remat = nn.remat

        def remat(fn, **kw):
            if getattr(fn, "__name__", "") != "pass_exit":
                return real_remat(fn, **kw)

            def pass_exit(mdl, x, *a):      # hands on what it was given
                normed, out = fn(mdl, x, *a)
                return x.astype(normed.dtype), out
            return real_remat(pass_exit, **kw)
        patches.append((nn, "remat", remat))
    elif fault == "uniform_exit":
        patches.append((lm, "exit_distribution", lambda logits: jnp.full(
            logits.shape, 1.0 / logits.shape[0], jnp.float32)))
    else:
        raise KeyError(f"unknown fault {fault!r}; have {FAULTS}")
    kept = [(where, name, getattr(where, name)) for where, name, _ in patches]
    for where, name, wrong in patches:
        setattr(where, name, wrong)
    try:
        yield
    finally:
        for where, name, real in kept:
            setattr(where, name, real)


def main() -> int:
    from benchmark.tools import sweep_first_steps

    at = sys.argv.index("--fault")
    fault = sys.argv[at + 1]
    del sys.argv[at:at + 2]
    with planted(fault):
        print(f"ouro_faults: {fault} planted in the program", flush=True)
        if "--tiny" in sys.argv:
            return tiny(fault)
        return sweep_first_steps.main()


def tiny(fault: str) -> int:
    """The rehearsal with the fault in: 0 where ``correct`` refuses it."""
    import io
    from contextlib import redirect_stdout

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmark import run as bench_run
    from benchmark.families.lm_looped_train import TINY

    said = io.StringIO()
    with redirect_stdout(said):
        out = bench_run.rehearse(CELL, 2 ** 31 + 11, 0.5, False, TINY)
    failed = [line.split()[1] for line in said.getvalue().splitlines()
              if line.startswith("check ") and line.endswith("FAILED")]
    print(f"ouro_faults: {fault} at the tiny sizes: correct "
          f"{out['correct']}, refused by {failed}", flush=True)
    return 0 if not out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
