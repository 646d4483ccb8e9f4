"""Layer: train step, device. Device time a step of the operations under the
``attention`` scope: the attention dispatch of ``ops/flash_attention.py`` — the
flash kernels and what XLA runs around them (layout changes, the backward's row
sums). Read by ``scope_time.py`` from the device trace joined with the
program's ``step_scopes`` table (self times, the train step's module only, a
mean over the chips). Nothing to read where the program recorded no table or
nothing ran in the scope."""

from benchmark.metrics.scope_time import read_metric


def read(ctx):
    return read_metric(ctx, "scope_attention_ms")
