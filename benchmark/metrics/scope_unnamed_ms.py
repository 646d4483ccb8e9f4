"""Layer: train step, device. Device time a step of the train step's operations to
which the compiler gave no name stack and which run in no loop or branch that
has one: copies, slices and broadcasts added at the step's top level. Read by
``scope_time.py`` from the device trace joined with the program's
``step_scopes`` table. Nothing to read where the program recorded no table."""

from benchmark.metrics.scope_time import read_metric


def read(ctx):
    return read_metric(ctx, "scope_unnamed_ms")
