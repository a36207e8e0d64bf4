"""Spans on the profiler clock, JSONL export, and JAX's compile time per span.

Three event kinds, all host-side Python (never traced inside ``jit``):

- **spans** — ``with trace.span("ckm.decode", decoder="clompr"):`` always
  enters a ``jax.profiler.TraceAnnotation`` of the same name (a no-op when no
  profiler session is active), so the program's own regions land in any
  profile beside the device ops, on the profiler's clock.  A thread-local
  stack of open spans gives each root span (one opened with nothing open on
  its thread) a process-unique ``req`` id that every span under it carries as
  an annotation argument, with its ``parent``'s name.  Under
  ``runtime.ENABLED`` the span is also recorded on the :class:`Tracer` as
  ``(name, t0, dur_s, depth, parent, req, attrs)``; ``t0`` is wall-clock
  ``time.time()``, the clock of ``jax.monitoring``'s time spans;
- **series** — a named list of floats, e.g. a decoder's per-round residual
  norms.  The values are computed *inside* the jitted decoder as ordinary
  array outputs (O(iterations) scalars, dead-code-eliminated when tracing is
  off) and handed to the tracer after the call — nothing is ever traced into
  the XLA graph;
- **points** — one-off ``(name, value, attrs)`` observations.

Compile time: a ``jax.monitoring`` time-span listener (with a scalar
listener for each stage's start), registered at import and always on, fires
only when JAX traces a function to a jaxpr, lowers it to MLIR, or compiles
it (a persistent-cache load counts as a compile).  It credits each stage to the innermost open span on the calling thread, less
the stages nested inside it (a jit traced inside another jit's trace is
counted once), so the self tallies of all spans add up to the host's compile
time.  On exit inside a profiler session a span attaches its tallies as
``trace_ms`` / ``lower_ms`` / ``compile_ms`` / ``jax_compiles``.  Under
``runtime.ENABLED`` each stage also lands on the ``jax.compile.seconds`` /
``jax.compile.events`` counters (labels ``stage``, ``span``) and on the
tracer as a ``jax.trace`` / ``jax.lower`` / ``jax.compile`` span event.

Series, points and span events are only recorded behind the switch.  Export
is JSON Lines: one self-describing object per event (``kind``/``name``/
``attrs`` plus kind-specific fields), parseable with nothing but
``json.loads`` per line.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import threading
import time
from pathlib import Path

import jax
import jax.monitoring

from repro.obs import metrics, runtime

__all__ = ["Tracer", "TRACER", "span", "series", "point", "export_jsonl"]

# jax.monitoring's compile-stage events -> index into a span's tallies.
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": 0,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": 1,
    "/jax/core/compile/backend_compile_duration": 2,
}
STAGES = ("trace", "lower", "compile")


class _Frame:
    """One open span: its identity and its self compile tallies."""

    __slots__ = ("name", "req", "parent", "secs", "events")

    def __init__(self, name: str, req: int, parent: str | None):
        self.name = name
        self.req = req
        self.parent = parent
        self.secs = [0.0, 0.0, 0.0]
        self.events = [0, 0, 0]

    def stats(self) -> dict:
        trace_s, lower_s, compile_s = self.secs
        return dict(trace_ms=1e3 * trace_s, lower_ms=1e3 * lower_s,
                    compile_ms=1e3 * compile_s, jax_compiles=self.events[2])


class _ThreadState(threading.local):
    def __init__(self):
        self.spans: list[_Frame] = []  # open spans, innermost last
        self.open_stages = 0  # compile stages started and not yet ended
        self.done: list[tuple[float, float]] = []  # ended inside open stages


_LOCAL = _ThreadState()
_REQ = itertools.count(1)


def _stage_started(event: str, value, **kwargs) -> None:
    if event in _STAGE_EVENTS:
        _LOCAL.open_stages += 1


def _stage_ended(event: str, t0: float, t1: float, **kwargs) -> None:
    stage = _STAGE_EVENTS.get(event)
    if stage is None:
        return
    loc = _LOCAL
    # Stages nest: every stage that ended since this one started lies inside
    # it, and its time was already credited.
    self_s, done = t1 - t0, loc.done
    while done and done[-1][0] >= t0:
        a, b = done.pop()
        self_s -= b - a
    loc.open_stages = max(loc.open_stages - 1, 0)
    if loc.open_stages:
        done.append((t0, t1))
    else:
        done.clear()
    frame = loc.spans[-1] if loc.spans else None
    if frame is not None:
        frame.secs[stage] += self_s
        frame.events[stage] += 1
    if runtime.ENABLED:
        where = frame.name if frame is not None else "none"
        metrics.counter("jax.compile.seconds", stage=STAGES[stage],
                        span=where).inc(self_s)
        metrics.counter("jax.compile.events", stage=STAGES[stage],
                        span=where).inc()
        TRACER.events.append({
            "kind": "span",
            "name": f"jax.{STAGES[stage]}",
            "t0": t0,
            "dur_s": t1 - t0,
            "depth": len(loc.spans),
            "parent": frame.name if frame is not None else None,
            "req": frame.req if frame is not None else None,
            "attrs": {"fun": kwargs.get("fun_name")},
        })


# JAX announces a stage's start as a scalar and its end as a time span.
jax.monitoring.register_scalar_listener(_stage_started)
jax.monitoring.register_event_time_span_listener(_stage_ended)


class Tracer:
    """Append-only event log; one process-wide instance at ``trace.TRACER``."""

    def __init__(self):
        self.events: list[dict] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """A span around a block of dispatch-layer code (module docstring).

        JAX dispatch is asynchronous, so a span around an un-synchronised
        call measures dispatch, not device compute; paths that block per
        batch (``fit_streaming``, ``ingest_stream``) give true durations.
        """
        stack = _LOCAL.spans
        if stack:
            up = stack[-1]
            frame = _Frame(name, up.req, up.name)
            ann = jax.profiler.TraceAnnotation(
                name, req=frame.req, parent=up.name, **attrs)
        else:
            frame = _Frame(name, next(_REQ), None)
            ann = jax.profiler.TraceAnnotation(name, req=frame.req, **attrs)
        record = runtime.ENABLED
        if record:
            t0, p0 = time.time(), time.perf_counter()
        stack.append(frame)
        try:
            with ann:
                try:
                    yield
                finally:
                    if ann.is_enabled():
                        ann.set_metadata(**frame.stats())
        finally:
            stack.pop()
            if record:
                self.events.append({
                    "kind": "span",
                    "name": name,
                    "t0": t0,
                    "dur_s": time.perf_counter() - p0,
                    "depth": len(stack),
                    "parent": frame.parent,
                    "req": frame.req,
                    "attrs": attrs,
                })

    def series(self, name: str, values, **attrs) -> None:
        """Record a convergence/trajectory series (list of floats)."""
        if not runtime.ENABLED:
            return
        self.events.append(
            {
                "kind": "series",
                "name": name,
                "values": [float(v) for v in values],
                "attrs": attrs,
            }
        )

    def point(self, name: str, value: float, **attrs) -> None:
        """Record a single observation."""
        if not runtime.ENABLED:
            return
        self.events.append(
            {
                "kind": "point",
                "name": name,
                "value": float(value),
                "attrs": attrs,
            }
        )

    def spans(self, name: str | None = None) -> list[dict]:
        """Completed span events, optionally filtered by name."""
        return [
            e
            for e in self.events
            if e["kind"] == "span" and (name is None or e["name"] == name)
        ]

    def jsonl_lines(self, metrics_snapshot: dict | None = None) -> list[str]:
        """Every event (plus an optional metrics snapshot) as JSONL lines."""
        lines = [json.dumps(e) for e in self.events]
        if metrics_snapshot is not None:
            for key, value in sorted(metrics_snapshot.items()):
                lines.append(
                    json.dumps({"kind": "metric", "name": key, "value": value})
                )
        return lines

    def export_jsonl(
        self, path, *, metrics_snapshot: dict | None = None
    ) -> Path:
        """Write the event log (and optional metrics) to a ``.jsonl`` file."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(
            "\n".join(self.jsonl_lines(metrics_snapshot)) + "\n"
        )
        return path

    def reset(self) -> None:
        self.events.clear()


TRACER = Tracer()


def span(name: str, **attrs):
    """``with obs.span("name", k=v):`` on the default tracer."""
    return TRACER.span(name, **attrs)


def series(name: str, values, **attrs) -> None:
    """Record a series on the default tracer."""
    TRACER.series(name, values, **attrs)


def point(name: str, value: float, **attrs) -> None:
    """Record a point observation on the default tracer."""
    TRACER.point(name, value, **attrs)


def export_jsonl(path, *, with_metrics: bool = True) -> Path:
    """Export the default tracer (and, by default, the metrics snapshot)."""
    snap = None
    if with_metrics:
        snap = metrics.snapshot()
    return TRACER.export_jsonl(path, metrics_snapshot=snap)
