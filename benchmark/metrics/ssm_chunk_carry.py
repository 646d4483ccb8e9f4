"""Layer: train step, device. What share of a state-space layer's state crosses
a chunk boundary: the program's ``ssm_chunk_carry`` counter, the mean over the
Mamba-2 layers, heads and chunks of ``exp(sum over the chunk's tokens of dt
A)``, a mean over the steps and the window's epochs. Near 0 the carried state
does nothing and the scan is its chunks' own products; near 1 nothing is ever
forgotten. Nothing to read where the program has no such counter."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "ssm_chunk_carry")
