"""Layer: input pipeline. Share of the traced window in which the least-busy chip
is idle and the innermost span of the program's ``train`` thread is
``data_wait`` or ``val_data_wait``: the chip waits for the loader
(``harness/span_clock.py``). With the other three ``idle_*`` shares it adds up
to ``device_idle_pct``."""

from benchmark.harness.span_clock import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx, "data_wait")
