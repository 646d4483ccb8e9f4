"""Layer: kernels. Summed length of the one-block flash kernels' operations
per optimizer step, a mean over the chips: the engagement counter of the
short-sequence form of ``ddw_tpu/ops/flash_attention.py``'s Pallas tier
(sequences under 512 tokens; ViT-B/16's 196). The device trace names a Mosaic
call by its ``pallas_call`` name, ``flash_short_fwd.1`` and
``flash_short_bwd.1`` (PR 29), names the streaming kernels do not use, so the
families are those two. Read from the ten longest families, like
``attention_kernel_ms``. Nothing to read where no such operation ran (a step
on the XLA tiers or on the streaming kernels, or a program without them)."""

KERNELS = ("flash_short_fwd", "flash_short_bwd")


def read(ctx):
    red, traced = ctx["reduced"], ctx["traced"]
    if not red or not traced or not traced.get("steps"):
        return None
    ns = sum(d for name, d in red["top_families"] if name in KERNELS)
    if not ns:
        return None
    return ns / traced["steps"] / 1e6
