"""Step-level tracer that works inside jitted code.

Three complementary mechanisms, all behind one :class:`ObsConfig`:

* **Host-side wall-clock spans** (:meth:`Tracer.span`) wrap whole
  dispatches — assembly, inference, force reduction, integration, scan
  windows, server batches.  Every span doubles as a
  ``jax.profiler.TraceAnnotation``, so the exact same phase names show up
  in real XLA profiles captured with :meth:`Tracer.start_capture`
  (``jax.profiler.start_trace``).  Inside the compiled programs the dd
  drivers (``obs.*``), the MD engine's step (``md.*``) and the DP model
  (``dp.*``) wrap their stages in ``jax.named_scope`` — zero runtime cost,
  pure HLO metadata.

* **Per-run host totals**: seconds per span name and host counters
  (:meth:`Tracer.count`, e.g. the engine's blocking ``host_reads``) since
  :meth:`Tracer.begin_run`; the MD engine publishes them as registry
  gauges at the end of each run.

* **Device-side per-step counters**: jitted step bodies assemble a small
  dict of scalars / short vectors out of the dd diag payloads
  (local/ghost counts, per-rank ``rank_cost``, neighbor occupancy,
  ``cost_max``/``cost_ratio``, rebuild + overflow flags); ``lax.scan``
  windows stack them along the step axis for free, and
  :meth:`Tracer.record_window` fetches the stacked arrays once per window
  boundary — one small host transfer per window, never a per-step sync.

Zero overhead when disabled: ``span`` returns one shared no-op context
manager and ``wants_counters`` is False so step bodies thread an *empty*
record dict — the traced program is identical and XLA dead-code-eliminates
every counter it would have carried.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
import warnings
from typing import Optional

import jax
import numpy as np

from .registry import Registry, get_registry


@dataclasses.dataclass
class ObsConfig:
    """Observability knobs (see README "Observability" knob matrix)."""

    enabled: bool = False       # master switch; False = hard zero-overhead
    counters: bool = True       # device-side per-step counter records
    spans: bool = True          # host wall-clock spans (+ TraceAnnotation)
    calibrate: bool = True      # accepted and ignored (probe timing retired)
    trace_dir: Optional[str] = None      # auto-flush events.jsonl here
    xla_trace_dir: Optional[str] = None  # jax.profiler.start_trace target
    max_events: int = 200_000   # event-buffer bound (drop + count past it)


class _NullSpan:
    """Shared no-op context manager — the disabled hot path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Wall-clock span + ``jax.profiler.TraceAnnotation`` (XLA visibility)."""

    __slots__ = ("_tracer", "_name", "_attrs", "_t0", "_anno")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs

    def __enter__(self):
        self._anno = jax.profiler.TraceAnnotation(self._name)
        self._anno.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._anno.__exit__(exc_type, exc, tb)
        tr = self._tracer
        dur = t1 - self._t0
        tr._add({"type": "span", "name": self._name,
                 "ts": self._t0 - tr._epoch, "dur": dur,
                 "tid": tr._tid(), **self._attrs})
        with tr._lock:
            tr._run_span_s[self._name] = (
                tr._run_span_s.get(self._name, 0.0) + dur)
        return False


def _jsonable(v):
    """numpy scalar/array -> plain int/float/bool/list for the JSONL log."""
    a = np.asarray(v)
    if a.ndim == 0:
        if a.dtype == bool:
            return bool(a)
        if np.issubdtype(a.dtype, np.integer):
            return int(a)
        return float(a)
    return a.tolist()


class Tracer:
    """One per engine/server; all layers report through it.

    Accepts an :class:`ObsConfig` (or another ``Tracer`` to share a buffer,
    or ``None`` for disabled).  Thread-safe: the serving worker and client
    threads append concurrently.
    """

    def __init__(self, config: Optional[ObsConfig] = None,
                 registry: Optional[Registry] = None):
        self.config = config if config is not None else ObsConfig()
        self.enabled = bool(self.config.enabled)
        self.registry = registry if registry is not None else get_registry()
        self.events: list[dict] = []
        self.dropped = 0
        self._lock = threading.Lock()
        self._tids: dict[int, int] = {}
        self._epoch = time.perf_counter()
        self._capturing = False
        self._run_span_s: dict[str, float] = {}
        self._run_counts: dict[str, int] = {}

    @staticmethod
    def ensure(obs) -> "Tracer":
        """Coerce an ``obs`` argument (Tracer | ObsConfig | None)."""
        if isinstance(obs, Tracer):
            return obs
        return Tracer(obs)

    @property
    def wants_counters(self) -> bool:
        """True when jitted step bodies should thread device counters."""
        return self.enabled and self.config.counters

    def _tid(self) -> int:
        ident = threading.get_ident()
        if ident not in self._tids:
            self._tids[ident] = len(self._tids)
        return self._tids[ident]

    def _add(self, ev: dict) -> None:
        with self._lock:
            if len(self.events) < self.config.max_events:
                self.events.append(ev)
            else:
                self.dropped += 1

    # -- event emission -----------------------------------------------------

    def meta(self, **attrs) -> None:
        if self.enabled:
            self._add({"type": "meta", **attrs})

    def instant(self, name: str, **attrs) -> None:
        if self.enabled:
            self._add({"type": "instant", "name": name,
                       "ts": time.perf_counter() - self._epoch, **attrs})

    def span(self, name: str, **attrs):
        """Context manager timing a host-side phase.  Disabled -> a shared
        null object: nothing allocated, nothing recorded."""
        if not (self.enabled and self.config.spans):
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to this run's host counter ``name`` (e.g. the engine's
        ``host_reads``).  Disabled -> nothing counted."""
        if self.enabled:
            with self._lock:
                self._run_counts[name] = self._run_counts.get(name, 0) + n

    def begin_run(self) -> None:
        """Zero the per-run span seconds and host counters."""
        with self._lock:
            self._run_span_s.clear()
            self._run_counts.clear()

    def run_totals(self) -> tuple[dict, dict]:
        """(seconds per span name, host counters) since :meth:`begin_run`."""
        with self._lock:
            return dict(self._run_span_s), dict(self._run_counts)

    def record_window(self, step0: int, n_steps: int, recs: dict) -> None:
        """Unpack per-step counters stacked by a ``lax.scan`` window.

        ``recs`` maps counter name -> array whose leading axis is the step
        axis (length ``n_steps``); one ``device_get`` moves the whole
        window, then each step becomes one ``step`` event at absolute step
        ``step0 + i``.
        """
        if not self.wants_counters or not recs:
            return
        self.count("host_reads")
        host = jax.device_get(recs)
        for i in range(n_steps):
            ev = {"type": "step", "step": int(step0) + i}
            for k, v in host.items():
                ev[k] = _jsonable(np.asarray(v)[i])
            self._add(ev)

    def record_step(self, step: int, rec: dict) -> None:
        """Single-step counter record (the per-step host loop)."""
        if not self.wants_counters or not rec:
            return
        self.count("host_reads")
        host = jax.device_get(rec)
        ev = {"type": "step", "step": int(step)}
        for k, v in host.items():
            ev[k] = _jsonable(v)
        self._add(ev)

    # -- XLA profile capture -------------------------------------------------

    def start_capture(self, trace_dir: Optional[str] = None) -> bool:
        """Start ``jax.profiler.start_trace`` into ``xla_trace_dir`` (or an
        explicit override).  Best-effort: never raises into the run."""
        d = trace_dir or self.config.xla_trace_dir
        if not (self.enabled and d) or self._capturing:
            return False
        try:
            jax.profiler.start_trace(d)
        except Exception as e:  # noqa: BLE001 — profiling must not kill MD
            warnings.warn(f"XLA trace capture unavailable: {e}",
                          stacklevel=2)
            return False
        self._capturing = True
        self.instant("xla_capture_start", dir=str(d))
        return True

    def stop_capture(self) -> bool:
        if not self._capturing:
            return False
        self._capturing = False
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            warnings.warn(f"XLA trace capture failed to stop: {e}",
                          stacklevel=2)
            return False
        self.instant("xla_capture_stop")
        return True

    # -- output -------------------------------------------------------------

    def flush(self, path: Optional[str] = None) -> Optional[str]:
        """Write the JSONL event log (validates the schema first)."""
        from . import export
        if path is None:
            if not self.config.trace_dir:
                return None
            path = os.path.join(self.config.trace_dir, "events.jsonl")
        with self._lock:
            events = list(self.events)
            if self.dropped:
                events.append({"type": "meta", "dropped_events": self.dropped})
        return export.write_jsonl(events, path)

    def chrome_trace(self, path: str) -> str:
        """Write the Perfetto-loadable Chrome-trace view of the spans."""
        from . import export
        with self._lock:
            events = list(self.events)
        return export.write_chrome_trace(events, path)

    def clear_steps(self) -> None:
        """Drop buffered per-step device-counter events (``type == "step"``).

        Step counters are per-run state, like the engine's ``timings``: a
        new ``run()`` on the same engine clears them so the previous
        trajectory's stale counters don't leak into the next trace (and
        restarted trajectories don't produce duplicate absolute step
        numbers).  Spans, meta and instant events survive — only the
        device-counter records are per-run."""
        with self._lock:
            self.events[:] = [e for e in self.events
                              if e.get("type") != "step"]

    def reset(self) -> None:
        with self._lock:
            self.events.clear()
            self.dropped = 0
        self._epoch = time.perf_counter()
