"""Layer: kernels. Summed length of the chosen-key attention kernels'
operations per optimizer step, a mean over the chips: the engagement counter
of ``ddw_tpu/ops/indexed_kernels.py`` (PR 33). The device trace names a Mosaic
call by its ``pallas_call`` name — ``indexed_fwd``, ``indexed_dq``,
``indexed_dkv``, ``indexed_target``, names no other kernel uses — so the
families are those four. Read from every operation of the traced window, not
from the ten longest families: the target pass is short. Nothing to read where
no such operation ran (a step on the XLA tiles, or a program without the
kernels)."""

from benchmark.harness.trace_reduce import op_family

KERNELS = ("indexed_fwd", "indexed_dq", "indexed_dkv", "indexed_target")


def read(ctx):
    record, traced = ctx.get("record"), ctx.get("traced")
    if not record or not record.get("devices") or not traced \
            or not traced.get("steps"):
        return None
    devices = record["devices"]
    ns = sum(d for events in devices.values() for name, _, d in events
             if op_family(name) in KERNELS) / len(devices)
    if not ns:
        return None
    return ns / traced["steps"] / 1e6
