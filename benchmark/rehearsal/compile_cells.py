#!/usr/bin/env python3
"""Compile-only rehearsal: each cell's train step, and the reference's
gradient block, at full size for a described ``v5e:2x2`` — no chip, nothing
runs (on-chip-measurement guide, section 2). Run by hand before a chip call:

    JAX_PLATFORMS=cpu python3 benchmark/rehearsal/compile_cells.py [cell ...]

Prints ``memory_analysis()`` per chip and the collectives in the program. What
the TPU compiler refuses here costs no chip time.
"""

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402
from jax.experimental import topologies                # noqa: E402
from jax.sharding import SingleDeviceSharding          # noqa: E402

from benchmark.harness.manifest import Cell, load_manifest   # noqa: E402
from benchmark.harness.trace_reduce import COLLECTIVES       # noqa: E402

GB = 1e9


def report(tag: str, compiled, t0: float):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    colls = {c: text.count(f" {c}(") + text.count(f" {c}-start(")
             for c in COLLECTIVES}
    print(f"{tag}: arguments {m.argument_size_in_bytes / GB:.2f} GB, "
          f"outputs {m.output_size_in_bytes / GB:.2f} GB, aliased "
          f"{m.alias_size_in_bytes / GB:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / GB:.2f} GB, code "
          f"{m.generated_code_size_in_bytes / 1e6:.0f} MB; collectives "
          f"{ {k: v for k, v in colls.items() if v} }; compiled in "
          f"{time.time() - t0:.0f} s", flush=True)


def main(names):
    manifest = load_manifest()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in names or [w["name"] for w in manifest["workloads"]]:
        cell = Cell(manifest, name)
        devices = list(topo.devices)[: cell.chips]
        t0 = time.time()
        report(f"{name} step", cell.family.compile_step(
            cell.config, cell.traffic, devices), t0)

        # the reference's gradient block, float32 and the fp8 control
        one = SingleDeviceSharding(devices[0])
        spec = cell.family.reference_spec(cell.config)
        weights = {k: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
                   for k, (shape, _) in spec.items()}
        batch = cell.family.reference_batch_shapes(cell.config, cell.traffic,
                                                   one)
        for precision in ("f32", "fp8"):
            t0 = time.time()
            loss = cell.family.reference_loss(cell.config, precision)
            report(f"{name} reference {precision}",
                   jax.jit(jax.value_and_grad(loss)).lower(
                       weights, *batch).compile(), t0)


if __name__ == "__main__":
    main(sys.argv[1:])
