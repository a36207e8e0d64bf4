"""``repro.obs`` — telemetry + sketch-health diagnostics for the CKM stack.

Three layers, documented (with runnable snippets) in ``docs/observability.md``:

- :mod:`repro.obs.runtime` — the master switch.  The tracer and the
  registry are inert until :func:`enable` flips the module-level
  ``runtime.ENABLED`` bool; the disabled hot path costs one attribute read +
  branch.  Spans reach the profiler either way; off, they and the compile
  listener cost ~0.6 ms of a 1.12 s paper-size fit on a TPU v5e host.
- :mod:`repro.obs.metrics` / :mod:`repro.obs.trace` — a get-or-create
  instrument registry (counters / gauges / histograms) and spans that
  always enter a ``jax.profiler.TraceAnnotation``, with JAX's compile time
  charged to the innermost open span, and a tracer with JSONL export.  The
  instrumented call sites live in ``core/ckm.py`` (the fit's spans),
  ``core/engine.py`` (update/merge/finalize), ``core/ingest.py`` (overlap
  accounting), ``serve/fleet_service.py`` (flush latency, decode-cache
  traffic) and the decoders (convergence series).
- :mod:`repro.obs.diagnose` — ``ckm.diagnose(result)``: attribute a bad fit
  to sketch size m, frequency scale sigma, or the decoder; plus the O(m)
  :func:`sketch_drift` score emitted as a gauge by ``FleetService.drift``
  and ``ActivationMonitor.sketch_drift``.
"""

from __future__ import annotations

from repro.obs import metrics, runtime, trace
from repro.obs.diagnose import (
    Diagnosis,
    diagnose,
    matched_distance,
    model_sketch,
    sigma_sweep,
    sketch_drift,
)
from repro.obs.metrics import (
    REGISTRY,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    snapshot,
)
from repro.obs.runtime import disable, enable, enabled, enabled_scope
from repro.obs.trace import TRACER, Tracer, export_jsonl, point, series, span

__all__ = [
    # switch
    "enable",
    "disable",
    "enabled",
    "enabled_scope",
    # metrics
    "MetricsRegistry",
    "REGISTRY",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    # tracing
    "Tracer",
    "TRACER",
    "span",
    "series",
    "point",
    "export_jsonl",
    # diagnostics
    "Diagnosis",
    "diagnose",
    "sketch_drift",
    "model_sketch",
    "matched_distance",
    "sigma_sweep",
    # submodules
    "metrics",
    "runtime",
    "trace",
    "reset",
]


def reset() -> None:
    """Reset the default metrics registry *and* the default tracer.

    One call returns the process to a clean-slate telemetry state (the
    switch position is left alone) — tests and benchmark trials use this
    between runs.
    """
    metrics.reset()
    trace.TRACER.reset()
