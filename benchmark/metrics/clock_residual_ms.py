"""Layer: device. Distance between the two anchors that join the program's
clock to the profile's, after the spans were shifted by the fetch anchor
(``harness/span_clock.py``): the true offset lies within it. Above 1 the four
``idle_*`` shares of gaps shorter than this are not to be trusted."""

from benchmark.harness.span_clock import idle_by_span


def read(ctx):
    found = idle_by_span(ctx)
    return None if found is None else found["clock"]["residual_ns"] / 1e6
