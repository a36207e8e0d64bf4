"""The telemetry master switch — one module-level bool, read on every hot path.

Spans always reach the profiler (``repro.obs.trace``); everything else —
recording events on the tracer and touching the metrics registry — sits
behind this switch.  Every instrumented call site guards with
``if runtime.ENABLED:`` *before* touching any telemetry object, so the
disabled path costs one module attribute read and a branch.  On a TPU v5e
host a span costs 3-4 us and the compile-stage listener ~1.2 us per stage
with the switch off: ~0.6 ms of a 1.12 s fit at the paper's size (15
spans, ~440 compile stages).
Nothing here is ever traced inside ``jit`` — instrumentation happens at the
Python dispatch layer, and convergence traces are computed *as array
outputs* of the jitted decoders and emitted host-side (see
``docs/observability.md``).

Call sites must read the flag as an attribute (``runtime.ENABLED``), never
``from ... import ENABLED`` — a from-import snapshots the value at import
time and would never see :func:`enable`.
"""

from __future__ import annotations

import contextlib

__all__ = ["ENABLED", "enable", "disable", "enabled", "enabled_scope"]

ENABLED: bool = False


def enable() -> None:
    """Turn telemetry on process-wide (metrics + tracer events)."""
    global ENABLED
    ENABLED = True


def disable() -> None:
    """Turn telemetry off; instrumented paths fall back to the bare hot path."""
    global ENABLED
    ENABLED = False


def enabled() -> bool:
    """The current switch state (prefer attribute reads on hot paths)."""
    return ENABLED


@contextlib.contextmanager
def enabled_scope(on: bool = True):
    """Scoped enable/disable — restores the previous state on exit."""
    global ENABLED
    prev = ENABLED
    ENABLED = on
    try:
        yield
    finally:
        ENABLED = prev
