"""Layer: kernels. The share of the streaming flash kernels' backward passes
that ran as ONE kernel (PR 45): 1 - (``flash_dq`` operations / ``flash_dkv``
operations) in the traced window, counted over every chip. The one-pass
backward of ``ddw_tpu/ops/flash_attention.py`` (a score tile, its exponential
and dS made once; dQ, dK and dV all from them) keeps the ``pallas_call`` name
``flash_dkv`` and runs no ``flash_dq``, so it reads 1.0; a program whose
backward is the two kernels, one operation each a call, reads 0.0, and one
that keeps the pair for some shapes reads the share of calls that did not.
The engagement counter of that mechanism: where it reads 1.0,
``attention_kernel_ms`` holds the forward kernel and the whole backward in
``flash_dkv``. Operations are counted, not their time. Nothing to read where
no ``flash_dkv`` operation ran (the one-block kernels, the XLA tiers, the
chosen-key kernels) or the run was not traced."""

from benchmark.harness.trace_reduce import op_family


def read(ctx):
    record = ctx.get("record")
    if not record or not record.get("devices"):
        return None
    count = {"flash_dq": 0, "flash_dkv": 0}
    for events in record["devices"].values():
        for name, _, _ in events:
            family = op_family(name)
            if family in count:
                count[family] += 1
    if not count["flash_dkv"]:
        return None
    return 1.0 - count["flash_dq"] / count["flash_dkv"]
