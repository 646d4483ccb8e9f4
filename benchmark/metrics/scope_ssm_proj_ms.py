"""Layer: train step, device. Device time a step of the operations under the
``ssm_proj`` scope: the Mamba-2 input and output projections
(``models/mamba.py``). Read by ``scope_time.py`` from the device trace joined
with the program's ``step_scopes`` table (self times, the train step's module
only, a mean over the chips). Nothing to read where the program recorded no
table or nothing ran in the scope."""

from benchmark.metrics.scope_time import read_metric


def read(ctx):
    return read_metric(ctx, "scope_ssm_proj_ms")
