"""How ``correct`` is decided for a training cell (PERF.md section 2).

The timed path's first two steps (``StepProbe``) against the plain float32
reference following the same two steps from the same seeded weights on the
same rows, at the timed batch. Every number is printed beside its limit; the
limits live in ``cells/<workload>.json`` and were set from chip readings of
sound runs and of the lower-precision control (``tools/sweep.py``).
"""

from __future__ import annotations

import math
import statistics


def norm_gap(program: dict, reference: dict) -> tuple:
    """Worst leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger (some gradients are all but zero)."""
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for key, ref in reference.items():
        gap = abs(program[key] - ref) / max(ref, floor)
        if not gap <= worst:            # NaN lands here and stays
            worst, where = gap, key
    return worst, where


QUIET = 1e-3    # a leaf whose first gradient is under this share of the
                # median leaf's is all but zero (a key bias: softmax does not
                # see it). Adam divides its rounding noise by that noise's own
                # size, so its change says nothing; it is left out of the
                # parameter-change comparison, and only of that one.


def compare_steps(program: dict, reference: dict) -> dict:
    """``program`` / ``reference``: ``losses``, ``grad_norms``, ``delta_norms``
    as ``StepProbe.collect`` and ``reference.optim.run_steps`` give them."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(program["losses"], reference["losses"]))
    grad_gap, grad_at = norm_gap(program["grad_norms"],
                                 reference["grad_norms"])
    floor = QUIET * statistics.median(reference["grad_norms"].values())
    loud = [k for k, g in reference["grad_norms"].items() if g >= floor]
    delta_gap, delta_at = norm_gap(
        {k: program["delta_norms"][k] for k in loud},
        {k: reference["delta_norms"][k] for k in loud})
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "delta_norm_gap": delta_gap,
            "_where": {"grad_norm_gap": grad_at, "delta_norm_gap": delta_at,
                       "quiet_leaves": len(reference["grad_norms"]) - len(loud)}}


def reference_steps(family, config: dict, seed: int, batches: list,
                    hyper: dict, micro: int, precision: str = "f32",
                    devices: list | None = None) -> dict:
    """Run the plain reference over the probe's batches. Nothing of the
    program's state may still be on the device when this is called. On
    several chips the rows of a block are spread over them and the weights are
    replicated (the compiler partitions the same plain code), so that a
    four-chip cell's check takes as long as a one-chip cell's."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.harness.weights import seed_key, seeded_weights
    from benchmark.reference.optim import run_steps

    devices = devices or jax.devices()[:1]
    mesh = Mesh(np.array(devices), ("rows",))
    whole, by_row = NamedSharding(mesh, P()), NamedSharding(mesh, P("rows"))
    spec = family.reference_spec(config)
    # the key is an argument, so that the program is the same for every seed
    weights = jax.jit(lambda key: seeded_weights(key, spec),
                      out_shardings=whole)(seed_key(seed))
    loss_fn = family.reference_loss(config, precision)
    micro = micro * len(devices)
    device_batches = [family.reference_batch(b) for b in batches]
    return run_steps(loss_fn, weights, device_batches, hyper, micro, by_row)


def judge(numbers: dict, limits: dict) -> tuple:
    """``numbers``: name -> value. Prints each beside its limit; a number with
    no limit in the cell's file is a fault of the cell, not a pass."""
    ok = True
    for name, value in numbers.items():
        if name.startswith("_"):
            continue
        if name not in limits:
            raise KeyError(f"cells file gives no limit for {name!r}")
        limit = limits[name]
        if isinstance(limit, list):
            passed = limit[0] <= value <= limit[1]
        else:
            passed = value <= limit
        passed = bool(passed) and math.isfinite(value)
        print(f"check {name} = {value!r} limit {limit!r} "
              f"{'ok' if passed else 'FAILED'}", flush=True)
        ok &= passed
    return ok
