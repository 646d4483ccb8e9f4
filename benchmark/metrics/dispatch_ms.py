"""Layer: train loop, host. Median length of the program's ``dispatch`` spans
that start inside the traced epoch: the call of the compiled step, enqueue and
back-pressure from the device queue together."""

from benchmark.harness.span_clock import median_ms


def read(ctx):
    return median_ms(ctx, "dispatch")
