"""Layer: kernels. What the attention kernels of a step have to compute over
what the chip could have computed in the time they took: 100 x the family's
``attention_kernel_flops_per_step`` (every block's causal pairs ``B heads S
(S + 1) / 2`` x 2 x (4 x the q/k head width + 3 x the v head width): the
seven products of forward and backward at the widths the mathematics needs)
over (``attention_kernel_ms``'s summed time of ``flash_fwd``, ``flash_dq``,
``flash_dkv`` x the chip's bf16 peak). Compute-bound: the operands of a block
are read once a grid step and reused across 512 x 1024 pairs. The dK/dV
kernel's second making of the scores, lanes a head's window pads (192 runs as
two lane rows) and masked pairs inside a diagonal block are work done and not
required, so the share cannot pass 100 %. Nothing to read where no such kernel
ran or the family has no such function."""

from benchmark.metrics import attention_kernel_ms


def read(ctx):
    ms = attention_kernel_ms.read(ctx)
    if not ms or not ctx.get("peaks"):
        return None
    import importlib

    family = importlib.import_module(
        "benchmark.families." + ctx["config"]["family"])
    required = getattr(family, "attention_kernel_flops_per_step", None)
    if required is None:
        return None
    # the kernels' time is a mean over the chips: a chip's own rows
    flops = required(ctx["config"], ctx["traffic"])
    return 100.0 * flops / (ms / 1e3 * ctx["peaks"]["bf16_flops_per_s"])
