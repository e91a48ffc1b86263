"""repro.obs — unified telemetry across AMU → pager → engine.

Observability on the engine's clock: its own
:class:`~repro.serve.config.VirtualClock` by default, or the wall clock
a deployment injects (``SchedulerConfig.clock``):

  * :class:`Tracer` — structured spans/instants/counter samples for
    every pager action, page residency transition, engine request
    lifecycle event and, on the virtual clock, every simulated AMU
    transfer (default-off-cheap: one branch).  ``Tracer.span`` times
    the engine's step phases and frame transfers as they happen; with
    ``Tracer.profile`` set each such span is also a
    ``jax.profiler.TraceAnnotation``, so it lands on the host plane of
    a profiler trace beside the device's operations (``profile`` alone
    writes only those annotations).  On an injected
    clock the AMU stays unbound: its simulated ticks are not wall time
    (no AMU spans, no ``amu/latency_s`` histogram),
  * :class:`MetricsRegistry` — counters, gauges, and log-bucketed
    :class:`Histogram` percentiles (p50/p95/p99/max); the subsystem
    ``stats`` Counters are now :class:`CounterView` windows onto it,
  * exporters — Chrome-trace/Perfetto JSON timelines
    (:func:`write_chrome_trace`) and flat metrics JSON
    (:func:`write_metrics`), the payloads behind
    ``launch/serve --trace-out/--metrics-out``.

``tools/trace_report.py`` consumes the timeline standalone: schema
validation, per-QoS queueing-delay breakdown, and an SLO attainment
report recomputed from trace events alone.
"""

from .metrics import CounterView, Histogram, MetricsRegistry
from .tracer import NULL_TRACER, Tracer
from .export import to_chrome_trace, write_chrome_trace, write_metrics

__all__ = [
    "CounterView", "Histogram", "MetricsRegistry",
    "NULL_TRACER", "Tracer",
    "to_chrome_trace", "write_chrome_trace", "write_metrics",
]
