"""The measured window of a training cell, driven from inside one ``fit``.

The trainers report each epoch to a tracker run (``log_metrics``) right after
they have fetched the epoch's mean losses from the device — a forced
device-to-host read of values that depend on every step of the epoch, so the
call marks a moment at which all of the epoch's work is done. ``EpochClock``
stands in the run's place, stamps those moments, and steers:

- epoch 0 compiles (or loads executables) and epoch 1 runs the same shapes
  once more, settled; both are set-up, and so are further settled epochs
  where the traffic asks for them (``warm_epochs``, 2 where it says nothing).
  At the last warm epoch's end the clock collects Python's garbage and
  freezes what is left (tracing two step programs leaves millions of
  objects; a full collection striking between two epochs stalls the
  dispatch, and one run in fourteen read a first window epoch of 3.79 s for
  2.83 s before this was here);
- the window opens at the last warm epoch's end and holds whole epochs until
  ``seconds`` have passed AND it holds ``window_epochs`` of them (0 where the
  traffic says nothing: the seconds alone decide); then the clock asks for
  the program's graceful stop (``runtime.faults.request_preemption``), which
  ``fit`` honours at its next dispatch by raising ``Preempted``;
- with ``trace_dir`` set, the profiler runs over the window's second epoch.

A cell whose required work drifts as it trains (routed experts on a chip's
share: the router learns the held experts, PERF.md section 2) takes both keys
in its traffic file, so that its window holds the same epochs on every seed
and machine; the other cells' windows are what they were.

Compilations are counted by ``jax.monitoring``; one inside the window makes
the run incorrect.
"""

from __future__ import annotations

import gc
import time

WARM_EPOCHS = 2     # the compiling epoch and one settled epoch are set-up,
                    # where the traffic does not say ``warm_epochs``


class CompileCounter:
    """Counts XLA compilations (cache hits included) while ``armed``."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.armed = False
        self.in_window: list = []
        self.total = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if "backend_compile" in event:
            self.total += 1
            if self.armed:
                self.in_window.append((event, duration))


class EpochClock:
    def __init__(self, seconds: float, compiles: CompileCounter,
                 request_stop, trace_dir: str = "",
                 probe_calls=lambda: 0, warm_epochs: int = WARM_EPOCHS,
                 window_epochs: int = 0):
        if warm_epochs < 2:
            raise ValueError("warm_epochs counts the compiling epoch and at "
                             f"least one settled one, got {warm_epochs}")
        self.warm = warm_epochs             # epochs that are set-up
        self.window_epochs = window_epochs  # the least the window holds
        self.trace_epoch = warm_epochs + 1  # the profiler runs over this one
        self.seconds = seconds
        self.compiles = compiles
        self.request_stop = request_stop
        self.trace_dir = trace_dir
        self.probe_calls = probe_calls
        self.ends: list = []            # perf_counter at each epoch's record
        self.rows: list = []
        self.traced = None              # filled when a trace was taken
        self._tracing = False

    # the tracker-run surface the trainers use
    def log_params(self, params):
        pass

    def log_metric(self, *args, **kwargs):
        pass

    def log_metrics(self, row, step=None):
        epoch = len(self.ends)
        if epoch == self.warm - 1:
            gc.collect()
            gc.freeze()
            self.compiles.armed = True
        now = time.perf_counter()
        self.ends.append(now)
        self.rows.append(dict(row))
        if self.trace_dir:
            if epoch == self.trace_epoch - 1:
                self._start_trace()
            elif epoch == self.trace_epoch:
                self._stop_trace()
        if (epoch >= self.warm - 1 and not self._tracing
                and now - self.ends[self.warm - 1] >= self.seconds
                and epoch - (self.warm - 1) >= self.window_epochs
                and (not self.trace_dir or self.traced is not None)):
            self.compiles.armed = False
            self.request_stop()

    def _start_trace(self):
        import jax

        options = jax.profiler.ProfileOptions()
        # device lines only. The host tracer, at any level, records the
        # runtime's ``Transpose`` events of every image batch's transfer (5.7
        # million in one ViT epoch: the epoch took 3.6 s for 1.56 s and the
        # trace 30 s to write), and tracing Python slows the host as well
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True
        self.traced = {"calls_before": self.probe_calls(),
                       "wall_start": time.time()}

    def _stop_trace(self):
        import jax

        self.traced["wall_end"] = time.time()
        self.traced["steps"] = self.probe_calls() - self.traced["calls_before"]
        jax.profiler.stop_trace()
        self._tracing = False

    def abandon_trace(self):
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    # the window, once fit has returned
    def window(self, steps_per_epoch: int, items_per_step: int) -> dict:
        ends = self.ends[self.warm - 1:]
        epochs = len(ends) - 1
        if epochs < 1:
            raise RuntimeError("the window holds no whole epoch")
        span = ends[-1] - ends[0]
        steps = epochs * steps_per_epoch
        return {"epochs": epochs, "steps": steps, "span_s": span,
                "items_per_s": steps * items_per_step / span,
                "epoch_s": [b - a for a, b in zip(ends, ends[1:])]}
