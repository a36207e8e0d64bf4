"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`load_xplane` keeps three event lists, on one clock:

- device ops: every event of each ``/device:TPU:<i>`` plane's ``XLA Ops``
  line, named by its HLO instruction (``fourier_sketch_kernel.1``);
- device modules: the ``XLA Modules`` line, named by the jitted program
  (``jit_clompr``);
- host spans: events of the host planes whose name is one the cell asked
  for (its own ``TraceAnnotation`` s and the program's spans).

Everything else works on those lists, so it is tested on a small recorded
trace kept as JSON.  An idle gap is a stretch of the window in which no
device op runs; it is charged to the innermost host span open at its
midpoint, or to ``host.other``.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    device: int = 0

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class TraceData:
    ops: list[Event]
    modules: list[Event]
    spans: list[Event]
    n_devices: int

    def window(self) -> tuple[float, float]:
        """The benchmark's measured window, from its ``bench.window`` span."""
        for s in self.spans:
            if s.name == WINDOW_SPAN:
                return s.start_ns, s.end_ns
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")

    def to_json(self) -> dict:
        """The form of ``tests/chipbench/data/fixture_trace.json``."""
        return {
            "n_devices": self.n_devices,
            **{
                k: [[e.name, e.start_ns, e.dur_ns, e.device] for e in getattr(self, k)]
                for k in ("ops", "modules", "spans")
            },
        }

    @classmethod
    def from_json(cls, d: dict) -> "TraceData":
        return cls(
            n_devices=d["n_devices"],
            **{k: [Event(*e) for e in d[k]] for k in ("ops", "modules", "spans")},
        )


def op_name(hlo_text: str) -> str:
    """``'%fusion.3 = f32[8]{0} fusion(...)'`` -> ``'fusion.3'``."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def module_name(name: str) -> str:
    """``'jit_clompr(1234)'`` -> ``'jit_clompr'``."""
    return name.split("(", 1)[0]


def load_xplane(log_dir: str, span_names) -> TraceData:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                   recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    wanted = set(span_names) | {WINDOW_SPAN}
    ops, modules, spans = [], [], []
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            devices += 1
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Event(op_name(e.name), e.start_ns, e.duration_ns, dev)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    modules.extend(Event(module_name(e.name), e.start_ns,
                                         e.duration_ns, dev)
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events if e.name in wanted)
    return TraceData(ops, modules, spans, max(devices, 1))


def merge_intervals(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to ``[lo, hi]``."""
    iv = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                if e.end_ns > lo and e.start_ns < hi)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _merged(tr: TraceData, dev: int) -> list[tuple[float, float]]:
    cache = tr.__dict__.setdefault("_merged_cache", {})
    if dev not in cache:
        cache[dev] = merge_intervals([e for e in tr.ops if e.device == dev],
                                     float("-inf"), float("inf"))
    return cache[dev]


def busy_ns(tr: TraceData, lo: float, hi: float) -> float:
    """Union of device op intervals in ``[lo, hi]``, averaged over devices."""
    total = 0.0
    for dev in range(tr.n_devices):
        iv = _merged(tr, dev)
        i = bisect.bisect_left(iv, (lo, lo))
        if i > 0 and iv[i - 1][1] > lo:
            i -= 1
        while i < len(iv) and iv[i][0] < hi:
            a, b = iv[i]
            total += max(0.0, min(b, hi) - max(a, lo))
            i += 1
    return total / tr.n_devices


def idle_gaps(tr: TraceData, lo: float, hi: float) -> list[tuple[float, float]]:
    """Stretches of the window in which device 0 runs no op."""
    gaps, t = [], lo
    for a, b in _merged(tr, 0):
        if b <= lo or a >= hi:
            continue
        a, b = max(a, lo), min(b, hi)
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def attribute_gaps(tr: TraceData, lo: float, hi: float) -> dict[str, float]:
    """Idle seconds by the innermost host span open at each gap's midpoint."""
    spans = sorted((s for s in tr.spans if s.name != WINDOW_SPAN),
                   key=lambda s: s.start_ns)
    out: dict[str, float] = defaultdict(float)
    active: list[Event] = []
    j = 0
    for a, b in idle_gaps(tr, lo, hi):  # in time order
        mid = 0.5 * (a + b)
        while j < len(spans) and spans[j].start_ns <= mid:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s.end_ns >= mid]
        name = min(active, key=lambda s: s.dur_ns).name if active else "host.other"
        out[name] += (b - a) * 1e-9
    return dict(out)


def op_seconds(tr: TraceData, lo: float, hi: float, match) -> float:
    """Device seconds of ops whose name satisfies ``match``, in the window
    (summed over devices)."""
    return 1e-9 * sum(
        min(e.end_ns, hi) - max(e.start_ns, lo)
        for e in tr.ops
        if match(e.name) and e.end_ns > lo and e.start_ns < hi
    )


def module_seconds(tr: TraceData, lo: float, hi: float, match) -> float:
    """Device seconds of programs whose name satisfies ``match``."""
    return 1e-9 * sum(
        min(e.end_ns, hi) - max(e.start_ns, lo)
        for e in tr.modules
        if match(e.name) and e.end_ns > lo and e.start_ns < hi
    )


def top_ops(tr: TraceData, lo: float, hi: float, k: int = 10):
    by: dict[str, float] = defaultdict(float)
    for e in tr.ops:
        if e.end_ns > lo and e.start_ns < hi:
            by[e.name] += (min(e.end_ns, hi) - max(e.start_ns, lo)) * 1e-9
    return sorted(([n, s] for n, s in by.items()), key=lambda x: -x[1])[:k]


def breakdown(tr: TraceData, lo: float, hi: float, k: int = 10) -> dict:
    gaps = attribute_gaps(tr, lo, hi)
    return {
        "device_ops": top_ops(tr, lo, hi, k),
        "idle_gaps": sorted(([n, s] for n, s in gaps.items()),
                            key=lambda x: -x[1])[:k],
    }
