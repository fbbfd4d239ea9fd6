"""Unified observability plane (DESIGN.md § Observability): metrics
registry + per-request trace spans + exporters. Dependency-free (numpy
only; an enabled tracer imports JAX for its profiler annotations) —
importable from kernels, serving, and benchmarks alike."""
from repro.obs.metrics import (Counter, Family, Gauge, Histogram,
                               ObsEvent, Registry, counter,
                               default_registry, emit_event, gauge,
                               histogram)
from repro.obs.trace import NULL_SPAN, NULL_TRACER, Span, Tracer
from repro.obs.export import (parse_prometheus, prometheus_families,
                              snapshot, snapshot_json, to_prometheus)

__all__ = [
    "Counter", "Family", "Gauge", "Histogram", "ObsEvent", "Registry",
    "counter", "default_registry", "emit_event", "gauge", "histogram",
    "NULL_SPAN", "NULL_TRACER", "Span", "Tracer",
    "parse_prometheus", "prometheus_families", "snapshot",
    "snapshot_json", "to_prometheus",
]
