"""Layer: train step, device. Device time a step of the operations under the
``attn_proj`` scope: the q/k/v and output projections with their own norms, the
rotary turn and the GQA broadcast (``models/lm.py::CausalSelfAttention``,
``models/vit.py::FlashMHA``). Read by ``scope_time.py`` from the device trace
joined with the program's ``step_scopes`` table (self times, the train step's
module only, a mean over the chips). Nothing to read where the program recorded
no table or nothing ran in the scope."""

from benchmark.metrics.scope_time import read_metric


def read(ctx):
    return read_metric(ctx, "scope_attn_proj_ms")
