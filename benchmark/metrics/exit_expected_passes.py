"""Layer: train step, device. Passes a token is expected to run before it
leaves, a mean over the window's epochs of the program's
``exit_expected_passes`` counter (``mean over tokens of sum_t t p_t``, ``p`` the
token's distribution over the exits that its gate gives: between 1 and the
number of passes; the trainer's row and its ``epoch`` span carry it). Training
runs every pass for every token whatever it reads: it is what early exit at
this gate would run at serving, and what the expected loss weighs the exits
by. Nothing to read where the program has no such counter.

A HEALTH counter: it moves no timing. The step's time and ``train_mfu``, which
the manifest's form makes it name under ``moves``, do not depend on the gate;
``better: lower`` is what a serving path that left at the gate's word would
pay, which this repo does not have (ROADMAP M11). From seeded weights the
window reads a gate that the trunk has already carried out of its seeded range
(PERF.md sections 6 and 7), not a trained one: no PR is better or worse by
this number."""

from benchmark.metrics.keys_per_query import window_mean


def read(ctx):
    return window_mean(ctx, "exit_expected_passes")
