"""Layer: input pipeline. Median length of the program's ``data_wait`` spans that
start inside the traced epoch: the train loop's wait for its next batch
(``next`` on the loader's iterator), a dispatch."""

from benchmark.harness.span_clock import median_ms


def read(ctx):
    return median_ms(ctx, "data_wait")
