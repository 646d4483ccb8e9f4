"""Layer: train loop, host. Share of the traced window in which the least-busy
chip is idle and the program's ``train`` thread is in any other span
(``train_fetch``, ``validation``, ``epoch_fetch``, ``epoch_report``,
``epoch_end``, ``ckpt_save``, ``epoch`` itself): the epoch's boundary
(``harness/span_clock.py``). With the other three ``idle_*`` shares it adds up
to ``device_idle_pct``."""

from benchmark.harness.span_clock import idle_share_pct


def read(ctx):
    return idle_share_pct(ctx, "boundary")
