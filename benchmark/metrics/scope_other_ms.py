"""Layer: train step, device. Device time a step of the train step's operations
that no scope metric of the cell counts: operations under a step-level scope
and no layer's (block norms, residual adds, the gradient's accumulation), under
``embed``, and under the layer scopes that take less than 2 % of this cell's
step and so have no line in ``BENCHMARK.json``. Computed as the step's
operation time less the cell's listed scope metrics and ``scope_unnamed_ms``
(``scope_time.py``), so a cell's scope metrics add up to its step. Nothing to
read where the program recorded no table."""

from benchmark.metrics.scope_time import read_metric


def read(ctx):
    return read_metric(ctx, "scope_other_ms")
