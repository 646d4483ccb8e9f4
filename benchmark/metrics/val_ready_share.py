"""Layer: input pipeline. Of an epoch's validation batches, the share that
were already transferred and waiting in the validation stream's queue when
the epoch loop asked for them: the program's ``val_ready_share`` counter
(``ddw_tpu/train/loop.py::run_epochs`` opens the epoch's validation stream
when the epoch's training begins, its first chain dispatched, and reads the
stream's own queue at each ask; PR 40), a mean over the window's epochs. 1.0
says the validation pass was read, decoded and moved while the chip trained;
0.0 that the chip waited for it after it drained. Nothing to read where the
program has no such counter."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "val_ready_share")
