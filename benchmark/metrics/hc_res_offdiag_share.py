"""Layer: train step, device. The mass of the hyper-connections' mixing matrix
``Hres`` off its diagonal over the number of streams: the program's
``hc_res_offdiag_share`` counter (``ddw_tpu/models/lm.py::HyperConnection``),
a mean over tokens, sublayers, steps and the window's epochs. 0 is a plain
residual (every stream keeps to itself), ``1 - 1/n`` (0.75 at four) the
streams mixed evenly: between them the streams carry different things and the
20 Sinkhorn rounds a token and sublayer do work that matters. Nothing to read
where the program has no such counter."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "hc_res_offdiag_share")
