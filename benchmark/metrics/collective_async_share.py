"""Layer: collectives. Of the all-reduces in the traced window, counted over
every chip, the share that ran as asynchronous collective fusions (PR 49):
``async-collective-done`` operations / (those + the synchronous reduces). The
data-parallel steps of ``ddw_tpu/train`` hand the compiler one reduce a
gradient leaf (``ddw_tpu/parallel/collectives.py``) and ask it to fuse them;
a reduce it fused shows in the chip's trace as the pair
``async-collective-start.N`` ... ``async-collective-done.N`` with the
fusions that carry it between (one reduce, counted once, at its ``done``),
and one it left alone as a single operation named ``all-reduce.N`` -- or
``psum.N`` where the reduce has one operand and keeps the program's name for
it -- for whose whole length the core stands still. (``all-reduce-start`` /
``all-reduce-done`` halves, which this compiler does not emit for these
steps, would count once, at the ``done``, among the synchronous: no fusion
carries them.) A step whose reduces all wait reads 0.0, one whose reduces all
ride 1.0; the two scalar means of the loss and the accuracy stay synchronous,
so a fully fused GPT-2 medium step reads 148 / 150. Operations are counted,
not their bytes nor their time: ``collective_ms`` has the time of what stayed
synchronous. Nothing to read on one chip, where no reduce runs, in a window
without a reduce, or untraced."""

from benchmark.harness.trace_reduce import op_family

ASYNC_DONE = "async-collective-done"
SYNCHRONOUS = ("all-reduce", "all-reduce-done", "psum")


def read(ctx):
    record = ctx.get("record")
    if not record or not record.get("devices") or ctx.get("chips", 0) < 2:
        return None
    fused = waited = 0
    for events in record["devices"].values():
        for name, _, _ in events:
            family = op_family(name)
            fused += family == ASYNC_DONE
            waited += family in SYNCHRONOUS
    if not fused + waited:
        return None
    return fused / (fused + waited)
