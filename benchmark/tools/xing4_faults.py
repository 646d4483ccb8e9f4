#!/usr/bin/env python3
"""The planted faults of ``xing4_train_s4096``: the program with one thing
wrong, through ``sweep_first_steps.py``'s own run of the cell. Each trains and
nothing looks broken; the ``correct`` comparison has to fail every one, and
this prints by which limits (PERF.md section 2). ``--fault`` names one:

- ``hres_identity``: the hyper-connections' mixing matrix replaced by the
  identity (``ddw_tpu/models/lm.py::sinkhorn`` put out of action): the streams
  never exchange anything, a plain residual four times over;
- ``no_mtp_term``: ``lambda = 0``, the multi-token-prediction module's
  cross-entropy left out of the loss that is descended
  (``train/lm_step.py``'s ``mtp_weight``);
- ``no_yarn_scale``: YaRN's factor ``(0.1 ln 64 + 1)^2`` left out of the
  latent attention's softmax scale (``ops/rope.py::yarn_softmax_factor``).

The other arguments are ``sweep_first_steps.py``'s; ``--tiny`` in their place
runs the cell's rehearsal (the family's tiny sizes, float32, on the CPU: no
device metric) with the fault planted and prints which limits refuse it.

    python3 benchmark/tools/xing4_faults.py --fault hres_identity \\
        --workload xing4_train_s4096 --seeds 11 --steps-per-epoch 2
    python3 benchmark/tools/xing4_faults.py --fault no_yarn_scale --tiny
"""

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

FAULTS = ("hres_identity", "no_mtp_term", "no_yarn_scale")


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` planted for the length of the block."""
    import jax.numpy as jnp

    from ddw_tpu.models import lm
    from ddw_tpu.ops import rope
    from ddw_tpu.train import lm_trainer

    if fault == "hres_identity":
        where, name = lm, "sinkhorn"
        wrong = lambda logits, iters, eps: jnp.broadcast_to(    # noqa: E731
            jnp.eye(logits.shape[-1], dtype=logits.dtype), logits.shape)
    elif fault == "no_mtp_term":
        where, name = lm_trainer, "make_lm_train_step"
        real = lm_trainer.make_lm_train_step
        wrong = lambda *a, **k: real(*a, **dict(k, mtp_weight=0.0))  # noqa: E731
    elif fault == "no_yarn_scale":
        where, name = rope, "yarn_softmax_factor"
        wrong = lambda factor: 1.0                              # noqa: E731
    else:
        raise KeyError(f"unknown fault {fault!r}; have {FAULTS}")
    kept = getattr(where, name)
    setattr(where, name, wrong)
    try:
        yield
    finally:
        setattr(where, name, kept)


def main() -> int:
    from benchmark.tools import sweep_first_steps

    at = sys.argv.index("--fault")
    fault = sys.argv[at + 1]
    del sys.argv[at:at + 2]
    with planted(fault):
        print(f"xing4_faults: {fault} planted in the program", flush=True)
        if "--tiny" in sys.argv:
            return tiny(fault)
        return sweep_first_steps.main()


def tiny(fault: str) -> int:
    """The rehearsal with the fault in: 0 where ``correct`` refuses it."""
    import io
    from contextlib import redirect_stdout

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmark import run as bench_run
    from benchmark.families.lm_latent_hc_moe_train import TINY

    said = io.StringIO()
    with redirect_stdout(said):
        out = bench_run.rehearse("xing4_train_s4096", 2 ** 31 + 11, 0.5,
                                 False, TINY)
    failed = [line.split()[1] for line in said.getvalue().splitlines()
              if line.startswith("check ") and line.endswith("FAILED")]
    print(f"xing4_faults: {fault} at the tiny sizes: correct "
          f"{out['correct']}, refused by {failed}", flush=True)
    return 0 if not out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
