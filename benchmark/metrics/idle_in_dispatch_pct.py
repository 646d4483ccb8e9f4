"""Layer: train loop, host. Share of the traced window in which the least-busy
chip is idle and the innermost span of the program's ``train`` thread is
``dispatch``, ``val_dispatch`` or ``train_chain`` itself: the chip waits for
the host's enqueue or the loop's own work (``harness/span_clock.py``). With
the other three ``idle_*`` shares it adds up to ``device_idle_pct``."""

from benchmark.harness.span_clock import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx, "dispatch")
