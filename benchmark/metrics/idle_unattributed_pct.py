"""Layer: device. Share of the traced window in which the least-busy chip is idle
and no span of the program's ``train`` thread covers the moment
(``harness/span_clock.py``). With the other three ``idle_*`` shares it adds up
to ``device_idle_pct``."""

from benchmark.harness.span_clock import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx, "unattributed")
