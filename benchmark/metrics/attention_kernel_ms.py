"""Layer: kernels. Summed length of the flash-attention kernels' operations
per optimizer step, a mean over the chips: the engagement counter of
``ddw_tpu/ops/flash_attention.py``'s Pallas tier. The device trace names a
Mosaic call by its ``pallas_call`` name (``flash_fwd.1``, ``flash_dq.1``,
``flash_dkv.1``: found with ``tools/fa2_sweep.py --profile``, PR 26), so the
families are those three. Read from the ten longest families, which these are
in wherever the kernels run in every layer. Nothing to read where no such
operation ran (a step on the XLA tiers, or a program without named kernels)."""

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx):
    red, traced = ctx["reduced"], ctx["traced"]
    if not red or not traced or not traced.get("steps"):
        return None
    ns = sum(d for name, d in red["top_families"] if name in KERNELS)
    if not ns:
        return None
    return ns / traced["steps"] / 1e6
