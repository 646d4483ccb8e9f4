"""Layer: train step, device. Rows the routed experts' dispatch ran over the
rows of its worst-case buffer, ``k * T``: the program's ``moe_rows_run_share``
counter (``ddw_tpu/models/moe.py::grouped_experts`` runs the buffer in chunks
of a row count taken from its shapes, as many as hold an assignment to a held
expert; PR 36), a mean over the routed layers, the steps and the window's
epochs. 1.0 is the whole buffer whatever the routing; a chip that holds a
sixteenth of its router's experts reads an eighth while they are given under
0.75 assignments a token. Nothing to read where the program has no such
counter."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "moe_rows_run_share")
