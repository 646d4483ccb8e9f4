"""Layer: collectives. What the core waits for the gradient's all-reduces per
optimizer step, worst chip, whichever way they run (PR 49): the summed length
of the synchronous reduces -- one operation each, named ``all-reduce.N``, or
``psum.N`` where the reduce has one operand and keeps the program's name, for
whose whole length the core stands still -- plus the summed length of the
``async-collective-done.N`` halves of the asynchronous fusion pairs, which is
how long each such reduce was NOT covered by the fusions that carried it (the
``async-collective-start.N`` halves only issue the transfer, and the carriers
between are compute that would run anyway). ``collective_ms`` reads names that
start with ``all-reduce`` alone: it misses the ``psum.N`` of a step whose
reduces are synchronous (7.2 of 28.4 ms on GPT-2 medium over four chips) and
everything of a step whose reduces are fused, so where the two differ this
one is the collective's exposed time. The families are
``collective_async_share``'s, which counts the same operations. Nothing to
read on one chip, in a window without a reduce, or untraced."""

from benchmark.harness.trace_reduce import op_family
from benchmark.metrics.collective_async_share import ASYNC_DONE, SYNCHRONOUS


def read(ctx):
    record, traced = ctx.get("record"), ctx.get("traced")
    if (not record or not record.get("devices") or ctx.get("chips", 0) < 2
            or not traced or not traced.get("steps")):
        return None
    waited = [sum(dur for name, _, dur in events
                  if op_family(name) == ASYNC_DONE
                  or op_family(name) in SYNCHRONOUS)
              for events in record["devices"].values()]
    if not max(waited):
        return None
    return max(waited) / traced["steps"] / 1e6
