"""Layer: train step, device. The share of the step's hyper-connected
sublayers that took the fused passes of ``ddw_tpu/ops/hyper_connection.py``
(one pass over the streams a side, a backward pass of their own): the
program's ``hc_fused_share`` counter (``ddw_tpu/models/lm.py::HyperConnection``
sows 1 or 0 a sublayer), a mean over sublayers, steps and the window's epochs.
1.0 where every sublayer's streams tile, 0.0 where all took the ``jnp`` forms.
Nothing to read where the program has no such counter."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "hc_fused_share")
