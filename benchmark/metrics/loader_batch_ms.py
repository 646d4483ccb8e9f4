"""Layer: input pipeline. Median length of the program's ``loader_batch`` spans
that start inside the traced epoch: one batch's read, decode and assembly on a
loader's producer thread (the train loader's and the validation loader's
batches alike)."""

from benchmark.harness.span_clock import median_ms


def read(ctx):
    return median_ms(ctx, "loader_batch")
