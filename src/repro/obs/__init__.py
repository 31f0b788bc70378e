"""``repro.obs`` — opt-in telemetry: metrics, tracing, convergence records.

Zero-overhead-when-disabled observability for the search engine, the serve
layer, and the verifiers:

* :class:`MetricRegistry` — labeled counters / gauges / histograms
  (:mod:`repro.obs.metrics`);
* :class:`Tracer` — span-scoped, schema-versioned JSONL events
  (``search`` -> ``generation`` -> ``batch_eval`` span nesting plus
  ``island.migration`` / ``serve.job`` / ``verify.*`` points;
  :mod:`repro.obs.trace`);
* :class:`Phases` — always-on calls and seconds per named hot-path phase
  (``pop.*``, ``ga.*``, ``session.*``), written into the JAX profiler's
  trace as ``TraceAnnotation`` spans while a profiler session records
  (:mod:`repro.obs.phase`);
* :class:`TelemetryCollector` — the hook surface instrumented layers call
  (:mod:`repro.obs.collect`);
* :mod:`repro.obs.clock` — the engine's single wall-clock seam (enforced
  by ``repro lint``'s ``clock-seam`` rule).

Activation is explicit: ``SearchSpec(telemetry=True)``, the ``--trace``
CLI flag, or ``REPRO_TRACE=path.jsonl`` in the environment.  Off is the
default and is dead cheap — instrumented modules hold ``None`` and skip
with one attribute check per *batch*, never per offspring — and enabling
telemetry changes no search result: store keys and fixed-seed RNG draw
sequences are bit-identical either way (pinned by tests).

``repro trace <file.jsonl>`` aggregates raw traces
(:mod:`repro.obs.traceview`); ``repro report --telemetry`` renders the
summary artifacts embed (:mod:`repro.obs.report`).

This package is stdlib-only and imports nothing from the engine, so
boundary-pinned checkers (``repro.analysis.verify``) may use it freely.
"""
from repro.obs import clock
from repro.obs.collect import (SUMMARY_SCHEMA, TRACE_ENV, TelemetryCollector,
                               trace_path_from_env)
from repro.obs.metrics import (NULL_REGISTRY, Counter, Gauge, Histogram,
                               MetricRegistry, NullRegistry)
from repro.obs.phase import Phases, merge_phases
from repro.obs.trace import (NULL_TRACER, SCHEMA_VERSION, NullTracer, Tracer,
                             validate_event)

__all__ = [
    "clock",
    "Counter", "Gauge", "Histogram", "MetricRegistry", "NullRegistry",
    "NULL_REGISTRY",
    "Tracer", "NullTracer", "NULL_TRACER", "SCHEMA_VERSION",
    "validate_event",
    "TelemetryCollector", "TRACE_ENV", "SUMMARY_SCHEMA",
    "trace_path_from_env",
    "Phases", "merge_phases",
]
