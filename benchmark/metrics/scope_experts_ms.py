"""Layer: train step, device. Device time a step of the operations under the
``experts`` scope: the routed experts of ``models/moe.py::RoutedExperts``: the
sort, the dispatch's gathers, the grouped products (``ragged-dot`` carries no
name stack and takes the scope of the dispatch loop it runs in), the way back.
Read by ``scope_time.py`` from the device trace joined with the program's
``step_scopes`` table (self times, the train step's module only, a mean over
the chips). Nothing to read where the program recorded no table or nothing ran
in the scope."""

from benchmark.metrics.scope_time import read_metric


def read(ctx):
    return read_metric(ctx, "scope_experts_ms")
