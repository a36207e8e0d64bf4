"""Helpers the per-layer metric readers (``metrics/<name>.py``) share."""

from __future__ import annotations

from chipbench import trace_reduce as tr


def window(ctx):
    """The traced window ``(lo_ns, hi_ns)``, or None without a trace."""
    if ctx.trace_data is None:
        return None
    return ctx.trace_data.window()


def kernel_seconds(ctx) -> float | None:
    """Device seconds of the Pallas sketch kernel in the window."""
    w = window(ctx)
    if w is None:
        return None
    s = tr.op_seconds(ctx.trace_data, *w, lambda name: "fourier_sketch" in name)
    return s if s > 0 else None


def module_seconds(ctx, prefixes) -> float | None:
    """Device seconds of the jitted programs named with one of ``prefixes``."""
    w = window(ctx)
    if w is None:
        return None
    s = tr.module_seconds(ctx.trace_data, *w,
                          lambda name: name.startswith(tuple(prefixes)))
    return s if s > 0 else None


def idle_percent(ctx) -> float | None:
    """Percent of the window in which no device op ran; None where the trace
    holds no device ops at all (no device plane was recorded)."""
    w = window(ctx)
    if w is None or not ctx.trace_data.ops:
        return None
    lo, hi = w
    return 100.0 * (1.0 - tr.busy_ns(ctx.trace_data, lo, hi) / (hi - lo))


def per(value, count, scale=1.0):
    if value is None or not count:
        return None
    return value * scale / count
