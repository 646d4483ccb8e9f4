"""Layer: train step, device. Keys a query attended to, a mean over the
window's epochs of the program's ``keys_per_query`` counter (a mean over the
layers that choose keys and over every query of every step; the trainer's
row and its ``epoch`` span carry it). A row of ``S`` tokens under ``topk``
gives ``mean_t min(t + 1, topk)`` and nothing else: 1,792.125 at 8,192 and
2,048. Nothing to read where the program has no such counter."""

from benchmark.harness.train_window import WARM_EPOCHS


def window_mean(ctx, counter):
    values = [row[counter]
              for row in ctx["rows"][ctx.get("warm_epochs", WARM_EPOCHS):]
              if counter in row]
    return sum(values) / len(values) if values else None


def read(ctx):
    return window_mean(ctx, "keys_per_query")
