"""Observability — tracing, the flight recorder, and the live telemetry plane.

One :class:`~ddw_tpu.obs.trace.Tracer` per process component (gateway,
replica engine, deploy controller, trainer) appends finished spans into a
bounded drop-oldest ring; exporters render the union as a Perfetto-loadable
Chrome trace (one track per replica/thread, flow events chaining each
request's spans across the fleet) or NDJSON for programmatic assertion.

The same components each hold a :class:`~ddw_tpu.obs.telemetry.
TelemetryHub` sampling counters/gauges/latency observations into windowed
time series (fleet-merged by the gateway), which the
:class:`~ddw_tpu.obs.slo.SLOMonitor` evaluates into error budgets,
burn-rate alerts, and degradation forensics dumps.

For the trainers, :mod:`ddw_tpu.obs.step_scopes` reads from the compiled
step which layer each of its operations belongs to, and the epoch loop
records that table as one span of a traced fit, so that a device profile can
be split by the program's own scopes. See docs/observability.md.
"""

from ddw_tpu.obs.slo import (  # noqa: F401
    SLOMonitor,
    SLOObjective,
)
from ddw_tpu.obs.telemetry import (  # noqa: F401
    FleetTelemetry,
    TelemetryHub,
    merge_feeds,
    signal_registry,
    tee_run,
)
from ddw_tpu.obs.trace import (  # noqa: F401
    Tracer,
    chrome_trace,
    gen_id,
    load_events,
    to_ndjson,
)

__all__ = ["Tracer", "chrome_trace", "gen_id", "load_events", "to_ndjson",
           "TelemetryHub", "FleetTelemetry", "merge_feeds",
           "signal_registry", "tee_run", "SLOMonitor", "SLOObjective"]
