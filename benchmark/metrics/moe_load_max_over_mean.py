"""Layer: train step, device. The fullest held expert's load over the held
experts' mean load, a mean over the routed layers, the steps and the window's
epochs (the program's ``moe_load_max_over_mean`` counter): 1.0 is an even
load; the grouped products' time follows the sum, a straggler chip in a
deployment follows the maximum. Nothing to read where the program has no such
counter."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "moe_load_max_over_mean")
