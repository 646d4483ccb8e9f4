"""Layer: train loop, host. Median length of the program's ``train_chain``
spans that start inside the traced window: one dispatch as the host sees it
(waiting for the batch, the enqueue, and back-pressure from the device queue
together; the program has no finer span yet)."""

import statistics


def read(ctx):
    traced = ctx["traced"]
    if not traced:
        return None
    lo, hi = traced["wall_start"] * 1e6, traced["wall_end"] * 1e6
    inside = [s["dur"] for s in ctx["spans"]
              if s["name"] == "train_chain" and lo <= s["ts"] <= hi]
    return statistics.median(inside) / 1e3 if inside else None
