"""Unified observability subsystem (paper Sec. VI's profiling methodology).

One instrumented spine every layer reports into:

* :mod:`repro.obs.registry` — process-wide counters / gauges / streaming
  histograms (p50/p90/p99, not just means); the serving layer's per-tenant
  metrics are built on these.
* :mod:`repro.obs.trace` — :class:`ObsConfig` + :class:`Tracer`: host-side
  wall-clock spans (doubling as ``jax.profiler.TraceAnnotation`` so phases
  show up in real XLA profiles), per-run host totals (span seconds, the
  engine's blocking host reads) that ``MDEngine.run`` publishes as
  ``md.run.*`` registry gauges, and device-side per-step/per-rank counters
  threaded through the dd diag payloads and carried out of ``lax.scan``
  windows as stacked arrays.
* :mod:`repro.obs.export` — JSONL event log + Chrome-trace (Perfetto) span
  export + schema validation.
* :mod:`repro.obs.report` — the paper's Fig. 12-style phase breakdown and
  per-rank load-imbalance tables rendered from a recorded trace file
  (``scripts/trace_report.py`` is the CLI).

Everything is off by default (``ObsConfig(enabled=False)``): the disabled
tracer returns a shared null span and an empty per-step record, so jitted
programs are bitwise-identical with and without the plumbing
(``benchmarks/dd_reuse.py`` measures the <2% overhead bound).
"""
from .registry import Counter, Gauge, Histogram, Registry, get_registry
from .trace import ObsConfig, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "get_registry",
    "ObsConfig", "Tracer",
]
