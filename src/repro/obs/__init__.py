"""Unified observability for the async-RL loop.

Three pillars, one import surface:

* ``obs.tracing`` — a low-overhead span tracer (``span(...)`` context
  manager, thread-aware, monotonic clocks) exporting Chrome/Perfetto
  ``trace.json``, with flow events tying a weight publish to the serving
  step that resumed under it; installed with ``annotate_jax=True`` its
  spans also land in a captured ``jax.profiler`` device profile.
* ``obs.metrics`` — a process-wide metrics registry (Counter / Gauge /
  Histogram with labels); ``serving.metrics.ServingMetrics`` is a thin
  facade over it and training-side metrics land in the same registry, so
  one ``registry.snapshot()`` serves the orchestrator, benchmarks, and
  tests.
* ``obs.runlog`` — a schema-versioned JSONL run log (one record per
  training step) behind the ``--log-jsonl``/``--quiet`` CLI surface.

``python -m repro.obs.report`` renders a run summary from the JSONL +
trace pair; ``python -m repro.obs.validate`` is the CI schema gate.
"""
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from repro.obs.runlog import (
    RUNLOG_SCHEMA_VERSION,
    STEP_REQUIRED_KEYS,
    RunLogger,
    step_record_dict,
)
from repro.obs.tracing import (
    SpanTracer,
    flow_end,
    flow_start,
    install_tracer,
    span,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "RUNLOG_SCHEMA_VERSION",
    "RunLogger",
    "STEP_REQUIRED_KEYS",
    "SpanTracer",
    "flow_end",
    "flow_start",
    "get_registry",
    "install_tracer",
    "span",
    "step_record_dict",
]
