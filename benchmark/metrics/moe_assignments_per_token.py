"""Layer: train step, device. Assignments to the experts held here a token, a
mean over the routed layers, the steps and the window's epochs (the program's
``moe_assignments_per_token`` counter). The deployment's expectation is
``num_experts_per_tok`` times the share of the router's experts held (1.0 in
``keyevl2_train_s8192``); on one chip's share only the held experts' output
reaches the loss, so the router learns to prefer them and the number rises as
the model trains. The grouped products' time follows it, and with it a step's
(PERF.md section 5). Nothing to read where the program has no such counter."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "moe_assignments_per_token")
