#!/usr/bin/env python3
"""The planted faults of ``joyai_flash_train_s8192``: the program with one
thing wrong, through ``sweep_first_steps.py``'s own run of the cell. Each
trains and nothing looks broken; the ``correct`` comparison has to fail every
one, and this prints by which limits (PERF.md section 2). ``--fault`` names
one:

- ``no_mtp_term``: ``lambda = 0``, the multi-token-prediction module's
  cross-entropy left out of the loss that is descended
  (``train/lm_step.py``'s ``mtp_weight``);
- ``no_routed_scale``: ``routed_scaling_factor`` 2.5 left out of the routed
  experts' weights (``models/moe.py::route_sigmoid``'s ``scale``): the routed
  part of every expert layer's output is 0.4 of what it should be;
- ``rope_key_per_head``: the rotary key part not shared: head ``h`` reads the
  one rotary key head rolled by ``h`` pairs, a key of its own, where all
  heads read the same 64 numbers (``models/lm.py::_latent`` hands the flash
  kernels one ``k_rope`` broadcast to the heads).

The other arguments are ``sweep_first_steps.py``'s; ``--tiny`` in their place
runs the cell's rehearsal (the family's tiny sizes, float32, on the CPU: no
device metric) with the fault planted and prints which limits refuse it.

    python3 benchmark/tools/joyai_flash_faults.py --fault no_routed_scale \\
        --workload joyai_flash_train_s8192 --seeds 11 --steps-per-epoch 2
    python3 benchmark/tools/joyai_flash_faults.py --fault rope_key_per_head --tiny
"""

import contextlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

FAULTS = ("no_mtp_term", "no_routed_scale", "rope_key_per_head")
CELL = "joyai_flash_train_s8192"


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` planted for the length of the block."""
    import jax.numpy as jnp

    from ddw_tpu.models import lm, moe
    from ddw_tpu.train import lm_trainer

    if fault == "no_mtp_term":
        where, name = lm_trainer, "make_lm_train_step"
        real = lm_trainer.make_lm_train_step
        wrong = lambda *a, **k: real(*a, **dict(k, mtp_weight=0.0))  # noqa: E731
    elif fault == "no_routed_scale":
        where, name = moe, "route_sigmoid"
        real = moe.route_sigmoid
        wrong = lambda logits, k, normalise, bias=None, scale=1.0: real(  # noqa: E731
            logits, k, normalise, bias, 1.0)
    elif fault == "rope_key_per_head":
        where, name = lm, "flash_mha_seq_major"
        real = lm.flash_mha_seq_major

        def wrong(q, k, v, **kw):
            rope = q.shape[-1] - v.shape[-1]    # a latent head's rotary part
            if rope > 0:
                own = jnp.stack([jnp.roll(k[:, :, h, -rope:], 2 * h, axis=-1)
                                 for h in range(k.shape[2])], axis=2)
                k = jnp.concatenate([k[..., :-rope], own], axis=-1)
            return real(q, k, v, **kw)
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
        print(f"joyai_flash_faults: {fault} planted in the program",
              flush=True)
        if "--tiny" in sys.argv:
            return tiny(fault)
        return sweep_first_steps.main()


def tiny(fault: str) -> int:
    """The rehearsal with the fault in: 0 where ``correct`` refuses it."""
    import io
    from contextlib import redirect_stdout

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from benchmark import run as bench_run
    from benchmark.families.lm_latent_moe_train import TINY

    said = io.StringIO()
    with redirect_stdout(said):
        out = bench_run.rehearse(CELL, 2 ** 31 + 11, 0.5, False, TINY)
    failed = [line.split()[1] for line in said.getvalue().splitlines()
              if line.startswith("check ") and line.endswith("FAILED")]
    print(f"joyai_flash_faults: {fault} at the tiny sizes: correct "
          f"{out['correct']}, refused by {failed}", flush=True)
    return 0 if not out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
