"""repro.obs — telemetry subsystem, dependency-free apart from JAX's
profiler (DESIGN.md §Observability).

  * :mod:`repro.obs.metrics` — counters / gauges / mergeable streaming-
    percentile histograms, a :class:`MetricsRegistry` with JSON-snapshot +
    Prometheus-text export, and the global ``enable()`` switch gating
    hot-path instrumentation;
  * :mod:`repro.obs.tracing` — ``span``, the program's host spans on the
    profiler's clock, and a span/event tracer with Chrome-trace export;
  * :mod:`repro.obs.meter`   — :class:`PhotonicMeter`, the live
    write-vs-reuse energy/latency ledger over ``core/costmodel.py``;
  * :mod:`repro.obs.stats`   — the shared ``WaveStats``/``ContinuousStats``
    protocol, registry-backed;
  * :mod:`repro.obs.serving` — request-lifecycle tracking (TTFT/TPOT/e2e)
    and the :class:`ServingObs` bundle the serving loop carries;
  * :mod:`repro.obs.check_schema` — the metrics-schema validator CLI.

Only ``metrics`` and ``tracing`` import eagerly (they are leaves —
``core/backend.py`` hooks them from inside the kernel-dispatch seam);
the model-aware modules load lazily to keep import edges acyclic.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter, CounterGroup, Gauge, Histogram, MetricsRegistry, counter,
    default_registry, disable, enable, enabled, gauge, histogram,
    record_kernel_call, reset_default_registry,
)
from repro.obs.tracing import (  # noqa: F401
    Tracer, default_tracer, enable_tracing, span,
)

_LAZY = {
    "PhotonicMeter": ("repro.obs.meter", "PhotonicMeter"),
    "StackProfile": ("repro.obs.meter", "StackProfile"),
    "ServingStats": ("repro.obs.stats", "ServingStats"),
    "WaveStats": ("repro.obs.stats", "WaveStats"),
    "ContinuousStats": ("repro.obs.stats", "ContinuousStats"),
    "RequestTracker": ("repro.obs.serving", "RequestTracker"),
    "ServingObs": ("repro.obs.serving", "ServingObs"),
    "validate_schema": ("repro.obs.check_schema", "validate"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod, attr = _LAZY[name]
        return getattr(importlib.import_module(mod), attr)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
