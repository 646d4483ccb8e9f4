"""Layer: kernels. The share of the chosen-key attention kernels' backward
passes that ran as ONE kernel (PR 46): 1 - (``indexed_dq`` operations /
``indexed_dkv`` operations) in the traced window, counted over every chip. The
one-pass backward of ``ddw_tpu/ops/indexed_kernels.py`` (a score tile, its
exponential and dS made once a head; dQ, dK and dV all from them) keeps the
``pallas_call`` name ``indexed_dkv`` and runs no ``indexed_dq``, so it reads
1.0; a program whose backward is the two kernels, one operation each a call,
reads 0.0, and one that keeps the pair for some shapes reads the share of
calls that did not. The engagement counter of that mechanism: where it reads
1.0, ``indexed_attention_kernel_ms`` holds the forward kernel, the target pass
and the whole backward in ``indexed_dkv``. Operations are counted, not their
time. Nothing to read where no ``indexed_dkv`` operation ran (the XLA tiles,
the streaming kernels' cells, ViT) or the run was not traced."""

from benchmark.harness.trace_reduce import op_family


def read(ctx):
    record = ctx.get("record")
    if not record or not record.get("devices"):
        return None
    count = {"indexed_dq": 0, "indexed_dkv": 0}
    for events in record["devices"].values():
        for name, _, _ in events:
            family = op_family(name)
            if family in count:
                count[family] += 1
    if not count["indexed_dkv"]:
        return None
    return 1.0 - count["indexed_dq"] / count["indexed_dkv"]
