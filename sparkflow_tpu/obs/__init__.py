"""Unified observability: spans, trace assembly, flight recorder, exporters.

- :mod:`~sparkflow_tpu.obs.spans` — ``Span``/``Tracer``: nested host-side
  timing with Chrome-trace / JSONL export and cross-thread propagation,
  plus ``TraceContext``: the W3C-traceparent-style context that carries a
  trace across processes.
- :mod:`~sparkflow_tpu.obs.collector` — ``TraceCollector``: router-side
  tail-sampled assembly of cross-process request timelines (one waterfall
  per kept request, Chrome-trace / JSONL export).
- :mod:`~sparkflow_tpu.obs.flight` — ``FlightRecorder``: always-on bounded
  crash flight recorder, dumped on SIGTERM/atexit and harvested by the
  ``ReplicaManager`` when a replica dies.
- :mod:`~sparkflow_tpu.obs.stepstats` — ``StepStats``: per-step phase
  breakdown (transfer / compile / step / metrics / checkpoint) + derived
  throughput and MFU gauges for ``Trainer.fit``.
- :mod:`~sparkflow_tpu.obs.exporters` — ``prometheus_text`` exposition of
  the whole metrics registry and the ``MemoryWatcher`` device-memory
  sampler.

See ``docs/observability.md`` for the end-to-end walkthrough.
"""

from .spans import (Span, TraceContext, Tracer, current_tracer,
                    default_tracer, phases, span)
from .stepstats import StepStats
from .collector import TraceCollector, trace_spans
from .flight import FlightRecorder, harvest_flight
from .exporters import MemoryWatcher, prometheus_name, prometheus_text

__all__ = [
    "Span", "TraceContext", "Tracer", "current_tracer", "default_tracer",
    "span", "phases",
    "StepStats",
    "TraceCollector", "trace_spans",
    "FlightRecorder", "harvest_flight",
    "MemoryWatcher", "prometheus_name", "prometheus_text",
]
