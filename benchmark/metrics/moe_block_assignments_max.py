"""Layer: train step, device. The fullest routed block's assignments to the
experts held here a token (the program's ``moe_block_assignments_max``
counter, ``train/lm_step.py::layer_terms``: the largest over the step's routed
blocks, the multi-token-prediction module's among them, of what
``moe_assignments_per_token`` is the mean of), a mean over the steps and the
window's epochs. A block's grouped products run a further chunk of
``chunk_rows`` once ITS held experts pass the chunk's share of a token's
choices, whatever the other blocks got, so this number, not the mean, says how
near the step stands to its next chunk; it is never under
``moe_assignments_per_token``. Nothing to read where the program has no such
counter."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "moe_block_assignments_max")
