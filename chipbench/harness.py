"""Find a cell's files by name, run its cell runner, print the result line.

A cell (``workloads`` entry of ``BENCHMARK.json``) names a configuration and
a traffic mix; the harness reads ``configs/<config>.json`` and
``traffic/<traffic>.json``, runs the cell runner ``cells/<kind>.py`` that the
configuration's ``kind`` names, and, in a traced run, the reader
``metrics/<metric>.py`` of every per-layer metric the cell reports.  A later
cell, traffic mix or metric is new files and new entries only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()  # set-up is measured from this import on

PACKAGE_DIR = Path(__file__).resolve().parent
CHECKOUT = PACKAGE_DIR.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    root: Path  # holds BENCHMARK.json and chipbench/
    bench: dict
    workload: dict
    config: dict
    traffic: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"] if self.name in
                m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        return [m for m in self.bench["per_layer"] if self.name in
                m.get("workloads", [self.name])]


def load_cell(workload: str, root: Path = CHECKOUT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; known: {sorted(by_name)}")
    wl = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[wl["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "chipbench" / "traffic" / f"{wl['traffic']}.json").read_text())
    return Cell(root, bench, wl, config, traffic)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``.  Every program is
    cached, however fast it compiled, so a warm run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's compile events (``jax.monitoring``), so a run can say
    how many programs were traced, compiled or loaded from the cache.  One
    per process (:func:`compile_counter`): listeners cannot be removed."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/backend_compile_duration": "compiles",
        "/jax/compilation_cache/cache_hits": "cache_hits",
    }

    def __init__(self):
        import jax.monitoring as mon

        self.counts = {v: 0 for v in self.EVENTS.values()}
        mon.register_event_duration_secs_listener(self._on)
        mon.register_event_listener(self._on)

    def _on(self, event, *args, **kwargs):
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


_COUNTER: CompileCounter | None = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def seed_key(seed: int):
    """A legacy uint32[2] PRNG key from any whole seed, large ones included."""
    import jax.numpy as jnp
    import numpy as np

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform}")
    if require_tpu and len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": min(chips, len(devices))}


def profile_options():
    """Profiler options of a traced window: host spans and device ops, no
    Python function tracing (which would slow the host several-fold)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``value <= limit`` passes."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class RunContext:
    """What a cell runner is given and what it hands back to the harness."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    trace_dir: str
    counter: CompileCounter
    # filled by the cell runner
    setup_s: float = 0.0
    end_to_end: dict = dataclasses.field(default_factory=dict)
    counts: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    window_compiles: dict = dataclasses.field(default_factory=dict)
    trace_data: object = None
    _mark: float = T_PROCESS

    def phase(self, name: str) -> None:
        """Log the set-up phase that ends now, timed from the last one."""
        now = time.perf_counter()
        log(f"[setup] {name}: {now - self._mark:.3f} s")
        self._mark = now


def per_layer_values(ctx: RunContext, device_kind: str) -> dict:
    """Run each per-layer metric's reader; a reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for m in ctx.cell.per_layer():
        reader = load_module(ctx.cell.root / "chipbench" / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx, device_kind)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = CHECKOUT, require_tpu: bool = True,
             trace_dir: str | None = None) -> dict:
    """Run one cell once; returns the result object (not yet printed)."""
    cell = load_cell(workload, root)
    if trace_dir is None:
        trace_dir = str(root / "chipbench_traces" / f"{workload}-{os.getpid()}")
    ctx = RunContext(cell, int(seed), float(seconds), bool(trace), trace_dir,
                     None)
    import jax

    ctx.phase("import jax")
    log(f"[setup] compile cache: {enable_compile_cache(root)}")
    device = device_info(cell.workload["chips"], require_tpu)
    ctx.phase("start the JAX runtime and find the device")
    log(f"[device] {device['count']} x {device['platform']} ({device['kind']}); "
        f"jax {jax.__version__}")
    ctx.counter = compile_counter()
    runner = load_module(root / "chipbench" / "cells" / f"{cell.config['kind']}.py")
    runner.run(ctx)
    ctx.end_to_end["setup_s"] = ctx.setup_s
    log(f"[counts] {json.dumps(ctx.counts)}")
    log(f"[window] compile events inside the window: "
        f"{json.dumps(ctx.window_compiles)}")
    log(f"[memory] peak_bytes_in_use {ctx.memory_peak_bytes}")
    device["memory_peak_bytes"] = ctx.memory_peak_bytes
    result = {
        "correct": all(c.ok for c in ctx.checks) and ctx.failed == 0,
        "attempted": ctx.attempted,
        "failed": ctx.failed,
    }
    if trace:
        from chipbench import trace_reduce as tr

        lo, hi = ctx.trace_data.window()
        device["busy_s"] = tr.busy_ns(ctx.trace_data, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        metrics = per_layer_values(ctx, device["kind"])
        result["breakdown"] = tr.breakdown(ctx.trace_data, lo, hi)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in cell.bench["end_to_end"]}
        metrics = {m["name"]: {"value": float(ctx.end_to_end[m["name"]]),
                               "unit": units[m["name"]]}
                   for m in cell.end_to_end()}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in ctx.checks}
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
