"""Layer: input pipeline. Median length of the program's ``loader_h2d`` spans
that start inside the traced epoch: a producer thread's transfer call for one
batch (``device_put`` or ``make_array_from_process_local_data``, and the
``raw_u8`` dequantise dispatch) as the host sees it."""

from benchmark.harness.span_clock import median_ms


def read(ctx):
    return median_ms(ctx, "loader_h2d")
