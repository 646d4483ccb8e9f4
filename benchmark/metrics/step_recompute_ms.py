"""Layer: train step, device. Device time a step of every operation whose name
stack holds ``rematted_computation``, across all scopes: a checkpointed block's
forward made again in the backward pass, what ``remat`` costs. Read by
``scope_time.py`` from the device trace joined with the program's
``step_scopes`` table. Nothing to read where the program recorded no table or
the step recomputes nothing."""

from benchmark.metrics.scope_time import read_metric


def read(ctx):
    return read_metric(ctx, "step_recompute_ms")
