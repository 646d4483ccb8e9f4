"""Layer: train step, device. The entropy of a token's distribution over the
exits, a mean over tokens, steps and the window's epochs of the program's
``exit_entropy`` counter (``mean H(p)`` in nats: 0 where every token leaves at
one exit, at most the logarithm of the number of passes; the trainer's row
and its ``epoch`` span carry it). The loss rewards it with
``exit_entropy_weight``; a gate that collapses to one exit reads 0 here before
the loss shows it. Nothing to read where the program has no such counter.

A HEALTH counter: it moves no timing. Training runs every pass for every token
whatever the gate says, so the step's time and ``train_mfu``, which the
manifest's form makes it name under ``moves``, do not depend on it; ``better:
higher`` says only that a collapsed gate is the fault to look for. From seeded
weights the window reads a gate that the trunk has already carried out of its
seeded range (PERF.md sections 6 and 7), not a trained one: no PR is better or
worse by this number."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "exit_entropy")
