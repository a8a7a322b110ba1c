"""repro.obs — the observability subsystem (tracing, metrics).

Two pillars, off by default:

* **tracing** (:mod:`repro.obs.trace` + :mod:`repro.obs.events`): nested
  wall-clock spans and typed events emitted from every layer of the stack
  (compile, dataflow legalisation, tuner, serving), exported to JSONL or
  Chrome ``trace_event`` JSON, and mirrored into the JAX profiler's trace
  as ``TraceAnnotation``s while a tracer records.  Enable with
  ``CompileOptions(trace=tracer)``, ``StencilEngine(tracer=...)``,
  ``set_tracer``, or ``REPRO_TRACE=path``.  :func:`phase` tags the device
  ops of the fused loop and the mesh by phase.
* **metrics** (:mod:`repro.obs.metrics`): counters/gauges/histograms with
  a JSON-ready ``snapshot()``; ``ServeStats`` is the serve-scoped view,
  :func:`global_metrics` collects the compile side.
"""

from .events import (CacheHit, CacheMiss, ChainDemoted, ExecutorEvicted,
                     PlanChosen, PlaneDemoted)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      global_metrics)
from .trace import (NULL, TRACE_ENV, NullTracer, Tracer, current_tracer,
                    phase, resolve_tracer, set_tracer)

__all__ = [
    "CacheHit", "CacheMiss", "ChainDemoted", "ExecutorEvicted",
    "PlanChosen", "PlaneDemoted",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "global_metrics",
    "NULL", "TRACE_ENV", "NullTracer", "Tracer", "current_tracer",
    "phase", "resolve_tracer", "set_tracer",
]
