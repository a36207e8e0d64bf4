"""The program's own spans in a traced window: the device's idle time inside
each, and the JAX compile time the program charged to each.

The program opens ``ckm.fit`` around each fit and ``ckm.sigma2``,
``ckm.operator``, ``ckm.ingest`` and ``ckm.decode`` inside it (``repro.obs``
spans, which are ``jax.profiler.TraceAnnotation`` s).  On exit each carries
the stats ``trace_ms``, ``lower_ms``, ``compile_ms`` and ``jax_compiles``:
the JAX compile stages that ran while it was the innermost open span.

:func:`spans` reads the host planes of the run's trace once and keeps them on
the run's context; the device ops and the window come from
``ctx.trace_data``.  Every reading is None where the trace holds no device
plane, or where the program opened none of these spans (a program from
before they existed).  The first read logs each span's time, self time,
idle time, self idle time and compile stats, per fit, to stderr.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import time

from chipbench import harness, readers
from chipbench import trace_reduce as tr

NAMES = ("ckm.fit", "ckm.sigma2", "ckm.operator", "ckm.ingest", "ckm.decode")
COMPILE_STATS = ("trace_ms", "lower_ms", "compile_ms")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_spans(log_dir: str) -> list[Span]:
    """The host events named in :data:`NAMES`, with their stats, in start
    order."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return []
    wanted = set(NAMES)
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend(
                    Span(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                    for e in line.events if e.name in wanted)
    return sorted(out, key=lambda s: s.start_ns)


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def inside(spans: list[Span], outer: list[Span]) -> list[Span]:
    """The spans that lie within one of ``outer`` and are not one of them."""
    starts = [s.start_ns for s in spans]
    own = {id(o) for o in outer}
    out = []
    for o in outer:
        i = bisect.bisect_left(starts, o.start_ns)
        while i < len(spans) and spans[i].start_ns <= o.end_ns:
            s = spans[i]
            if id(s) not in own and s.end_ns <= o.end_ns:
                out.append(s)
            i += 1
    return out


def spans(ctx) -> list[Span] | None:
    """The program spans that lie in the window, read once per run."""
    w = readers.window(ctx)
    if w is None or not ctx.trace_data.ops:
        return None
    if getattr(ctx, "program_spans", None) is None:
        t0 = time.perf_counter()
        lo, hi = w
        ctx.program_spans = [s for s in load_spans(ctx.trace_dir)
                             if s.start_ns >= lo and s.end_ns <= hi]
        harness.log(f"[trace] program spans read: "
                    f"{time.perf_counter() - t0:.3f} s")
        log_summary(ctx)
    return ctx.program_spans or None


def idle_ns(ctx, chosen: list[Span], minus: list[Span] = ()) -> float:
    """Device-idle time inside the union of ``chosen`` less that of
    ``minus`` (spans that lie inside ``chosen``)."""
    lo, hi = ctx.trace_data.window()
    gaps = tr.idle_gaps(ctx.trace_data, lo, hi)
    return (overlap_ns(gaps, tr.merge_intervals(chosen, lo, hi))
            - overlap_ns(gaps, tr.merge_intervals(minus, lo, hi)))


def idle_ms_per_fit(ctx, names) -> float | None:
    """Device-idle milliseconds per fit inside the spans named ``names``."""
    got = spans(ctx)
    chosen = [s for s in got or () if s.name in names]
    if not chosen:
        return None
    return readers.per(idle_ns(ctx, chosen) * 1e-9, ctx.counts.get("fits"),
                       1e3)


def compile_ms_per_fit(ctx) -> float | None:
    """JAX trace + lower + compile milliseconds per fit charged to the
    program's spans (each stage counted once, in the innermost span)."""
    got = [s for s in spans(ctx) or () if "trace_ms" in s.stats]
    if not got:
        return None
    total = sum(float(s.stats.get(k, 0.0)) for s in got for k in COMPILE_STATS)
    return readers.per(total, ctx.counts.get("fits"))


def log_summary(ctx) -> None:
    fits = ctx.counts.get("fits") or 1
    got = ctx.program_spans
    for name in NAMES:
        own = [s for s in got if s.name == name]
        if not own:
            continue
        kids = inside(got, own)
        lo, hi = ctx.trace_data.window()
        span_ns = sum(t - f for f, t in tr.merge_intervals(own, lo, hi))
        kids_ns = sum(t - f for f, t in tr.merge_intervals(kids, lo, hi))
        stats = {k: round(sum(float(s.stats.get(k, 0)) for s in own) / fits, 3)
                 for k in (*COMPILE_STATS, "jax_compiles")}
        harness.log(
            f"[program span] {name}: {len(own) / fits:g} per fit, "
            f"{span_ns * 1e-6 / fits:.3f} ms per fit "
            f"(self {(span_ns - kids_ns) * 1e-6 / fits:.3f}), "
            f"device idle {idle_ns(ctx, own) * 1e-6 / fits:.3f} ms "
            f"(self {idle_ns(ctx, own, kids) * 1e-6 / fits:.3f}); "
            f"compile stats per fit {stats}")
