"""Telemetry + diagnostics suite (``repro.obs``).

Covers the observability PR's acceptance criteria:

- metrics registry / tracer semantics, and the disabled-path no-op contract
  (nothing recorded, results bitwise identical to an untelemetered run);
- the fit's spans reach the profiler with telemetry off (one ``req`` per
  fit), and JAX's compile time lands on the span that compiled, once;
- instrumentation: engine update/merge/finalize spans + counters, ingest
  overlap accounting, FleetService flush/decode-cache/drift instruments;
- an enabled ``fit_streaming`` run emits update/merge/finalize spans and a
  decoder-convergence series, all parseable back from the JSONL export;
- ``ckm.diagnose`` attributes the three seeded failure modes (m too small,
  sigma mis-scaled, decoder under-iterated) and returns ``ok`` on a
  converged fit;
- the drift gauges distinguish a stationary stream from a mean-shifted one;
- FleetService decode-cache accounting matches a hand-simulated LRU over a
  scripted request sequence, version-bump invalidation included.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core import ckm as ckm_mod
from repro.core import fleet as fl
from repro.core import freq_ops as fo
from repro.core import ingest as ingest_mod
from repro.core.decoders.clompr import CLOMPRConfig, clompr
from repro.core.decoders.sketch_shift import SketchShiftConfig, sketch_shift
from repro.core.engine import SketchEngine
from repro.obs import metrics as obs_metrics
from repro.obs import runtime as obs_rt
from repro.serve.fleet_service import FleetService
from repro.train.monitor import ActivationMonitor

pytestmark = pytest.mark.obs

FAST = dict(atom_steps=40, joint_steps=30, nnls_iters=40, final_steps=80,
            shift_steps=40, shift_polish_steps=100)


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with telemetry off and empty stores."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(scope="module")
def blobs3():
    """Three well-separated 2-D blobs (N=3000) + a fitted reference config."""
    kc = jax.random.normal(jax.random.PRNGKey(5), (3, 2)) * 6.0
    idx = jax.random.randint(jax.random.PRNGKey(0), (3000,), 0, 3)
    pts = kc[idx] + 0.3 * jax.random.normal(jax.random.PRNGKey(6), (3000, 2))
    return np.asarray(pts)


def _op(m=32, n=3, seed=0):
    return fo.make_operator(
        "dense", jax.random.PRNGKey(seed), m, n, jnp.asarray(1.0)
    )


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_metrics_get_or_create_identity():
    c1 = obs.counter("x.calls", backend="xla")
    c2 = obs.counter("x.calls", backend="xla")
    c3 = obs.counter("x.calls", backend="pallas")
    assert c1 is c2 and c1 is not c3
    c1.inc()
    c1.inc(2.5)
    c3.inc()
    snap = obs.snapshot()
    assert snap["x.calls{backend=xla}"] == 3.5
    assert snap["x.calls{backend=pallas}"] == 1.0


def test_gauge_and_histogram_semantics():
    g = obs.gauge("g")
    g.set(1.0)
    g.set(0.25)
    h = obs.histogram("lat")
    for v in (0.5, 2.0, 0.004):
        h.observe(v)
    snap = obs.snapshot()
    assert snap["g"] == 0.25
    assert snap["lat"]["count"] == 3
    assert snap["lat"]["min"] == 0.004 and snap["lat"]["max"] == 2.0
    assert snap["lat"]["mean"] == pytest.approx((0.5 + 2.0 + 0.004) / 3)


def test_registry_reset_bumps_generation():
    gen0 = obs_metrics.REGISTRY.generation
    obs.counter("a").inc()
    obs_metrics.reset()
    assert obs_metrics.REGISTRY.generation == gen0 + 1
    assert obs.snapshot() == {}


def test_enabled_scope_restores():
    assert not obs_rt.ENABLED
    with obs_rt.enabled_scope():
        assert obs_rt.ENABLED
        with obs_rt.enabled_scope(False):
            assert not obs_rt.ENABLED
        assert obs_rt.ENABLED
    assert not obs_rt.ENABLED


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_span_noop_when_disabled():
    with obs.span("nothing"):
        pass
    obs.series("s", [1.0])
    obs.point("p", 2.0)
    assert obs.TRACER.events == []


def test_span_nesting_depth_and_jsonl(tmp_path):
    obs.enable()
    with obs.span("outer", tag="a"):
        with obs.span("inner"):
            pass
    obs.series("conv", [3.0, 2.0, 1.0], decoder="clompr")
    obs.point("pt", 7.0)
    obs.counter("c").inc(4)
    path = obs.export_jsonl(tmp_path / "t.jsonl")
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    spans = {e["name"]: e for e in lines if e["kind"] == "span"}
    assert spans["outer"]["depth"] == 0 and spans["outer"]["attrs"] == {"tag": "a"}
    assert spans["inner"]["depth"] == 1
    assert spans["outer"]["dur_s"] >= spans["inner"]["dur_s"]
    series = [e for e in lines if e["kind"] == "series"]
    assert series[0]["values"] == [3.0, 2.0, 1.0]
    metric = [e for e in lines if e["kind"] == "metric"]
    assert metric[0]["name"] == "c" and metric[0]["value"] == 4.0


# ---------------------------------------------------------------------------
# Spans on the profiler clock, and compile time per span
# ---------------------------------------------------------------------------


def _host_events(log_dir) -> dict:
    """``{name: [(start_ns, end_ns, stats)]}`` of a profile's host events."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _profiled(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def test_fit_spans_reach_the_profiler_with_telemetry_off(tmp_path, blobs3):
    cfg = ckm_mod.CKMConfig(k=3, m=60, **FAST)
    batches = [blobs3[i * 1000:(i + 1) * 1000] for i in range(3)]
    with _profiled(tmp_path):
        ckm_mod.fit_streaming(jax.random.PRNGKey(4), iter(batches), cfg)
    assert obs.TRACER.events == [] and obs.snapshot() == {}
    ev = _host_events(tmp_path)
    (fit,) = ev["ckm.fit"]
    assert len(ev["ckm.sigma2"]) == len(ev["ckm.decode"]) == 1
    assert len(ev["ckm.operator"]) == 2  # the draw, then quantizer + engine
    assert sorted(e[2]["chunk"] for e in ev["ckm.ingest"]) == [0, 1, 2]
    children = [e for name in ("ckm.sigma2", "ckm.operator", "ckm.ingest",
                               "ckm.decode") for e in ev[name]]
    assert all(fit[0] <= a and b <= fit[1] for a, b, _ in children)
    assert all(st["req"] == fit[2]["req"] and st["parent"] == "ckm.fit"
               for _, _, st in children)
    assert "parent" not in fit[2]
    decode = ev["ckm.decode"][0][2]
    assert decode["decoder"] == "clompr" and decode["replicates"] == 1
    for _, _, st in [fit, *children]:
        assert {"trace_ms", "lower_ms", "compile_ms", "jax_compiles"} <= set(st)


def test_compile_time_lands_on_the_span_that_compiled(tmp_path):
    import jax.monitoring as mon

    traces = []

    def listen(event, t0, t1, **kw):
        if event.endswith("jaxpr_trace_duration"):
            traces.append((kw.get("fun_name"), t0, t1))

    @jax.jit
    def inner(v):
        return jnp.cos(v) + 1.0

    @jax.jit
    def outer_fn(v):
        return inner(v) * 2.0

    x = jnp.arange(7.0)
    obs.enable()
    mon.register_event_time_span_listener(listen)
    try:
        with _profiled(tmp_path):
            with obs.span("outer"):
                with obs.span("first"):
                    outer_fn(x).block_until_ready()
                with obs.span("again"):
                    outer_fn(x).block_until_ready()
    finally:
        mon.unregister_event_time_span_listener(listen)
    obs.disable()
    st = {name: evs[0][2] for name, evs in _host_events(tmp_path).items()
          if name in ("outer", "first", "again")}
    assert st["first"]["trace_ms"] > 0 and st["first"]["compile_ms"] > 0
    assert st["first"]["jax_compiles"] >= 1
    for other in ("outer", "again"):
        assert st[other]["trace_ms"] == st[other]["compile_ms"] == 0
        assert st[other]["jax_compiles"] == 0
    # The inner jit is traced inside the outer one's trace: the span holds
    # the union of the two, the outer trace's length, not their sum.
    (_, a, b), = [t for t in traces if t[0] == "outer_fn"]
    assert [t for t in traces if t[0] == "inner" and a <= t[1] and t[2] <= b]
    assert st["first"]["trace_ms"] == pytest.approx(1e3 * (b - a), rel=1e-6)
    snap = obs.snapshot()
    assert snap["jax.compile.seconds{span=first,stage=trace}"] \
        == pytest.approx(b - a, rel=1e-6)
    assert snap["jax.compile.events{span=first,stage=compile}"] \
        == st["first"]["jax_compiles"]
    assert not any("span=again" in k or "span=outer" in k for k in snap)
    stages = [e for e in obs.TRACER.spans() if e["name"].startswith("jax.")]
    assert {e["parent"] for e in stages} == {"first"}
    assert {e["name"] for e in stages} == {"jax.trace", "jax.lower",
                                           "jax.compile"}


def test_fit_bitwise_equal_with_telemetry_on_and_off(blobs3):
    cfg = ckm_mod.CKMConfig(k=3, m=60, **FAST)
    batches = [blobs3[i * 1000:(i + 1) * 1000] for i in range(3)]
    off = ckm_mod.fit_streaming(jax.random.PRNGKey(9), iter(batches), cfg)
    assert obs.TRACER.events == [] and obs.snapshot() == {}
    obs.enable()
    on = ckm_mod.fit_streaming(jax.random.PRNGKey(9), iter(batches), cfg)
    obs.disable()
    assert obs.TRACER.spans("ckm.decode")
    assert jnp.array_equal(off.centroids, on.centroids)
    assert jnp.array_equal(off.weights, on.weights)
    assert jnp.array_equal(off.sketch, on.sketch)


# ---------------------------------------------------------------------------
# Engine instrumentation
# ---------------------------------------------------------------------------


def test_engine_disabled_path_is_silent_and_identical(rng):
    eng = SketchEngine(_op())
    x = jax.random.normal(rng, (64, 3))
    z0, lo0, hi0 = eng.sketch(x)
    assert obs.TRACER.events == [] and obs.snapshot() == {}
    obs.enable()
    z1, lo1, hi1 = eng.sketch(x)
    obs.disable()
    assert jnp.array_equal(z0, z1) and jnp.array_equal(lo0, lo1)


def test_engine_spans_and_counters(rng):
    eng = SketchEngine(_op())
    x = jax.random.normal(rng, (50, 3))
    obs.enable()
    state = eng.update(eng.init_state(), x)
    state = eng.update(state, x[:20])
    state = eng.merge(state, eng.init_state())
    eng.finalize(state)
    obs.disable()
    snap = obs.snapshot()
    assert snap["engine.update.calls{backend=xla,bits=none}"] == 2
    assert snap["engine.update.rows{backend=xla,bits=none}"] == 70
    assert snap["engine.finalize.calls{backend=xla,bits=none}"] == 1
    assert snap["engine.state.bytes{backend=xla,bits=none}"] > 0
    names = [e["name"] for e in obs.TRACER.spans()]
    assert names.count("engine.update") == 2
    assert names.count("engine.merge") == 1
    assert names.count("engine.finalize") == 1


def test_engine_quantized_labels(rng):
    from repro.core import quantize as qz

    q = qz.make_quantizer(jax.random.PRNGKey(3), 32, "1bit")
    eng = SketchEngine(_op(), quantizer=q)
    obs.enable()
    eng.sketch(jax.random.normal(rng, (40, 3)))
    obs.disable()
    assert obs.snapshot()["engine.update.rows{backend=xla,bits=1}"] == 40


def test_engine_handles_survive_registry_reset(rng):
    eng = SketchEngine(_op())
    x = jax.random.normal(rng, (8, 3))
    obs.enable()
    eng.update(eng.init_state(), x)
    obs.reset()  # stale handles must be re-resolved, not incremented orphaned
    eng.update(eng.init_state(), x)
    obs.disable()
    assert obs.snapshot()["engine.update.calls{backend=xla,bits=none}"] == 1


# ---------------------------------------------------------------------------
# Ingest instrumentation
# ---------------------------------------------------------------------------


def test_ingest_stats_surface_as_metrics(rng):
    eng = SketchEngine(_op())
    batches = [np.asarray(jax.random.normal(jax.random.fold_in(rng, i), (32, 3)))
               for i in range(5)]
    obs.enable()
    state, stats = ingest_mod.ingest_stream(eng, batches, prefetch=2)
    obs.disable()
    snap = obs.snapshot()
    assert snap["ingest.batches"] == stats.batches == 5
    assert snap["ingest.points"] == stats.points == 160
    assert snap["ingest.compute_s"] == pytest.approx(stats.compute_s)
    assert 0.0 <= snap["ingest.overlap_efficiency"] <= 1.0
    assert snap["ingest.resident_batches"] == 4  # prefetch + 2
    assert obs.TRACER.spans("ingest.stream")


def test_ingest_silent_and_identical_when_disabled(rng):
    eng = SketchEngine(_op())
    batches = [np.asarray(jax.random.normal(jax.random.fold_in(rng, i), (16, 3)))
               for i in range(3)]
    state, _ = ingest_mod.ingest_stream(eng, batches)
    assert obs.snapshot() == {} and obs.TRACER.events == []
    obs.enable()
    state2, _ = ingest_mod.ingest_stream(eng, batches)
    obs.disable()
    z0, _, _ = eng.finalize(state)
    z1, _, _ = eng.finalize(state2)
    assert jnp.array_equal(z0, z1)


# ---------------------------------------------------------------------------
# Decoder convergence traces
# ---------------------------------------------------------------------------


def _sketch_for_decode(blobs3, m=60):
    op = fo.make_operator(
        "dense", jax.random.PRNGKey(1), m, 2, jnp.asarray(0.2)
    )
    eng = SketchEngine(op)
    z, lo, hi = eng.sketch(jnp.asarray(blobs3))
    return z, op, lo, hi


def test_clompr_trace_output_and_parity(blobs3):
    z, op, lo, hi = _sketch_for_decode(blobs3)
    cfg = CLOMPRConfig(k=3, atom_steps=40, joint_steps=30, nnls_iters=40,
                       final_steps=80)
    c0, a0, cost0 = clompr(jax.random.PRNGKey(2), z, op, lo, hi, cfg)
    out = clompr(jax.random.PRNGKey(2), z, op, lo, hi,
                 dataclasses.replace(cfg, trace=True))
    c1, a1, cost1, traces = out
    # Tracing must not perturb the decode (buffers are DCE'd when off).
    assert jnp.array_equal(c0, c1) and jnp.array_equal(cost0, cost1)
    res = np.asarray(traces["residual_norm"])
    assert res.shape == (2 * cfg.k,) and np.all(np.isfinite(res))
    # Greedy pursuit: the final residual is far below the first round's.
    assert res[-1] < res[0]


def test_sketch_shift_trace_output(blobs3):
    z, op, lo, hi = _sketch_for_decode(blobs3)
    cfg = SketchShiftConfig(k=3, candidates=6, shift_steps=30,
                            polish_steps=50, nnls_iters=40, trace=True)
    _, _, _, traces = sketch_shift(jax.random.PRNGKey(2), z, op, lo, hi, cfg)
    res = np.asarray(traces["residual_norm"])
    assert res.shape == (3,) and np.all(np.isfinite(res))
    # Deflation: each harvested mode shrinks the residual.
    assert res[-1] < res[0]


def test_decode_sketch_emits_series_when_enabled(blobs3):
    z, op, lo, hi = _sketch_for_decode(blobs3)
    cfg = ckm_mod.CKMConfig(k=3, m=60, trace_convergence=True, **FAST)
    c0, a0, cost0 = ckm_mod.decode_sketch(
        jax.random.PRNGKey(2), z, op, lo, hi, cfg
    )
    obs.enable()
    c1, a1, cost1 = ckm_mod.decode_sketch(
        jax.random.PRNGKey(2), z, op, lo, hi, cfg
    )
    obs.disable()
    assert jnp.array_equal(c0, c1) and jnp.array_equal(cost0, cost1)
    series = [e for e in obs.TRACER.events if e["kind"] == "series"]
    assert [e["name"] for e in series] == ["decoder.clompr.residual_norm"]
    assert len(series[0]["values"]) == 2 * cfg.k


def test_decode_sketch_traces_best_replicate(blobs3):
    z, op, lo, hi = _sketch_for_decode(blobs3)
    cfg = ckm_mod.CKMConfig(k=3, m=60, replicates=2, decoder="sketch_shift",
                            trace_convergence=True, **FAST)
    obs.enable()
    _, _, cost = ckm_mod.decode_sketch(
        jax.random.PRNGKey(2), z, op, lo, hi, cfg
    )
    obs.disable()
    series = [e for e in obs.TRACER.events if e["kind"] == "series"]
    assert len(series) == 1 and len(series[0]["values"]) == cfg.k
    # The emitted trace belongs to the *selected* replicate: its last
    # residual-norm squared is the reported pre-polish cost scale (loose
    # sanity: finite, positive, same order as sqrt(cost)).
    assert series[0]["values"][-1] > 0.0


# ---------------------------------------------------------------------------
# fit_streaming end-to-end acceptance (spans + series from JSONL)
# ---------------------------------------------------------------------------


def test_fit_streaming_jsonl_acceptance(tmp_path, blobs3):
    cfg = ckm_mod.CKMConfig(k=3, m=60, trace_convergence=True, **FAST)
    batches = [blobs3[i * 500:(i + 1) * 500] for i in range(6)]
    obs.enable()
    res = ckm_mod.fit_streaming(jax.random.PRNGKey(1), iter(batches), cfg)
    path = obs.export_jsonl(tmp_path / "run.jsonl")
    obs.disable()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    spans = [e for e in lines if e["kind"] == "span"]
    span_names = {e["name"] for e in spans}
    assert {"engine.update", "engine.finalize", "ckm.fit", "ckm.sigma2",
            "ckm.operator", "ckm.ingest", "ckm.decode"} <= span_names
    root = next(e for e in spans if e["name"] == "ckm.fit")
    assert root["parent"] is None and root["depth"] == 0
    assert all(e["req"] == root["req"] for e in spans)
    assert {e["parent"] for e in spans if e["name"] == "engine.update"} \
        == {"ckm.ingest"}
    series = [e for e in lines if e["kind"] == "series"]
    assert any(e["name"] == "decoder.clompr.residual_norm" for e in series)
    vals = next(e for e in series
                if e["name"] == "decoder.clompr.residual_norm")["values"]
    assert len(vals) == 2 * cfg.k and all(np.isfinite(v) for v in vals)
    metrics = {e["name"]: e["value"] for e in lines if e["kind"] == "metric"}
    assert metrics["engine.update.rows{backend=xla,bits=none}"] == 3000
    # The run itself must be unperturbed by telemetry.
    res2 = ckm_mod.fit_streaming(jax.random.PRNGKey(1), iter(batches), cfg)
    assert jnp.array_equal(res.centroids, res2.centroids)


# ---------------------------------------------------------------------------
# FleetService accounting + drift
# ---------------------------------------------------------------------------


def _fleet_service(cache_entries=2, n_tenants=3, m=32, n=2, decode_cfg=None,
                   decay=None, drift_threshold=None):
    specs = fl.fleet_specs(jax.random.PRNGKey(0), n_tenants, "dense", m, n, 1.0)
    eng = fl.FleetEngine(specs, decay=decay)
    cfg = decode_cfg or ckm_mod.CKMConfig(
        k=2, decoder="sketch_shift", shift_candidates=2, shift_steps=3,
        shift_polish_steps=2, nnls_iters=4,
    )
    return FleetService(eng, cfg, decode_cache_entries=cache_entries,
                        drift_threshold=drift_threshold)


def test_fleet_lru_accounting_matches_hand_simulation(rng):
    """Scripted request sequence vs a hand-simulated LRU: hit/miss/evict
    counters must match *exactly*, version bumps invalidating as counted."""
    from collections import OrderedDict

    svc = _fleet_service(cache_entries=2)
    batch = lambda t, i: np.asarray(
        jax.random.normal(jax.random.fold_in(rng, 10 * t + i), (16, 2))
    )
    # (op, tenant): "w" = submit+flush (version bump), "d" = decode.
    script = [("w", 0), ("w", 1), ("w", 2),
              ("d", 0), ("d", 0),            # miss, hit
              ("d", 1),                      # miss (cache: {0, 1})
              ("d", 2),                      # miss, evicts 0 (LRU)
              ("d", 0),                      # miss again (was evicted)
              ("w", 1), ("d", 1),            # version bump -> miss
              ("d", 2), ("d", 2)]            # miss (evicted above), then hit
    sim = OrderedDict()
    versions = {0: 0, 1: 0, 2: 0}
    exp_hits = exp_misses = exp_evicts = 0
    obs.enable()
    for i, (op_, t) in enumerate(script):
        if op_ == "w":
            svc.submit(t, batch(t, i))
            svc.flush()
            versions[t] += 1
        else:
            r = svc.decode(t)
            key = (t, versions[t])
            if key in sim:
                exp_hits += 1
                sim.move_to_end(key)
                assert r.cached
            else:
                exp_misses += 1
                sim[key] = True
                sim.move_to_end(key)
                while len(sim) > 2:
                    sim.popitem(last=False)
                    exp_evicts += 1
                assert not r.cached
            assert r.version == versions[t]
    obs.disable()
    assert svc.stats.decode_hits == exp_hits == 2
    assert svc.stats.decode_misses == exp_misses == 6
    assert svc.stats.decode_cache_evictions == exp_evicts == 4
    assert svc.cache_len() == len(sim) <= 2
    snap = obs.snapshot()
    assert snap["fleet.decode.hits"] == exp_hits
    assert snap["fleet.decode.misses"] == exp_misses
    assert snap.get("fleet.decode.cache_evictions", 0) == exp_evicts
    assert snap["fleet.flush.seconds"]["count"] == svc.stats.flushes > 0


def test_fleet_drift_gauge_stationary_vs_shifted(rng):
    # A converged decode: the stationary drift is then just the (small)
    # decode residual, so the mean-shift signal stands clear of it.
    svc = _fleet_service(
        cache_entries=4, m=48,
        decode_cfg=ckm_mod.CKMConfig(k=2, m=48, shift_steps=40,
                                     shift_polish_steps=100, nnls_iters=50),
    )
    blob = lambda c, s: jnp.asarray(c) + 0.2 * jax.random.normal(
        jax.random.fold_in(rng, s), (300, 2)
    )
    svc.submit(0, blob([3.0, 3.0], 1))
    svc.submit(0, blob([-3.0, -3.0], 2))
    svc.flush()
    svc.decode(0)
    obs.enable()
    stationary = svc.drift(0)
    svc.submit(0, blob([9.0, 9.0], 3))  # mean shift: stream left the model
    svc.flush()
    shifted = svc.drift(0)
    obs.disable()
    assert shifted > 2.0 * stationary
    assert obs.snapshot()["fleet.drift{tenant=0}"] == pytest.approx(shifted)


def test_fleet_drift_redecode_counter(rng):
    """ISSUE 9: unattended maintenance — when a decayed fleet's flush sees a
    tenant breach drift_threshold it invalidates + re-decodes, and the event
    lands both in stats.drift_redecodes and the fleet.redecode.drift
    counter.  Also pins the all-zero-sketch regression: drift on a fresh
    tenant is a defined 0.0 gauge, never NaN."""
    svc = _fleet_service(
        cache_entries=4, m=48, decay=0.5, drift_threshold=0.25,
        decode_cfg=ckm_mod.CKMConfig(k=2, m=48, shift_steps=40,
                                     shift_polish_steps=100, nnls_iters=50),
    )
    blob = lambda c, s: jnp.asarray(c) + 0.2 * jax.random.normal(
        jax.random.fold_in(rng, s), (300, 2)
    )
    svc.submit(0, blob([3.0, 3.0], 1), t=0.0)
    svc.flush()
    svc.decode(0)
    assert svc.stats.drift_redecodes == 0
    obs.enable()
    # Four ticks of decay (old mass -> 6%) plus a mean shift: the served
    # model is now stale, the auto-maintain on flush must catch it.
    svc.submit(0, blob([9.0, -9.0], 2), t=4.0)
    svc.flush()
    obs.disable()
    assert svc.stats.drift_redecodes >= 1
    snap = obs.snapshot()
    assert snap["fleet.redecode.drift"] == svc.stats.drift_redecodes

    # Regression (ISSUE 9): an all-zero live sketch has nothing to drift
    # from — score and gauge are a defined 0.0, with no decode attempted.
    obs.enable()
    score = svc.drift(1)
    obs.disable()
    assert score == 0.0 and not np.isnan(score)
    assert obs.snapshot()["fleet.drift{tenant=1}"] == 0.0


# ---------------------------------------------------------------------------
# ActivationMonitor satellites
# ---------------------------------------------------------------------------


def test_monitor_freq_op_resolution():
    assert ActivationMonitor(dim=512, k=2, m=64).freq_op == "structured"
    assert ActivationMonitor(dim=8, k=2, m=64).freq_op == "dense"
    mon = ActivationMonitor(dim=1024, k=2, m=64, freq_op="dense")
    assert mon.freq_op == "dense"  # explicit override wins
    # The structured default must not materialize an (m, d) matrix in state.
    big = ActivationMonitor(dim=1024, k=2, m=64)
    assert big.freqs.state_bytes() < 64 * 1024 * 4


def test_monitor_sketch_drift_gauge(rng):
    mon = ActivationMonitor(dim=8, k=2, m=64)
    st = mon.init_state()
    x = jax.random.normal(rng, (400, 8))
    st = mon.update(st, x)
    res = mon.decode(st)
    obs.enable()
    stationary = mon.sketch_drift(st, res)
    shifted = mon.sketch_drift(mon.update(st, x + 5.0), res)
    obs.disable()
    assert shifted > 1.5 * stationary
    assert obs.snapshot()["monitor.sketch_drift"] == pytest.approx(shifted)


# ---------------------------------------------------------------------------
# ckm.diagnose — seeded failure-mode attribution (the PR's acceptance test)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_diagnose_attributes_seeded_failure_modes(blobs3):
    pts = blobs3
    # clompr at a mid budget for the seeded-failure fits; the healthy fit is
    # a well-converged sketch_shift decode of the same sketch size.
    base = dict(k=3, m=60, atom_steps=60, joint_steps=40, nnls_iters=60,
                final_steps=120)

    # -- converged fit -> ok ------------------------------------------------
    good = ckm_mod.fit(
        jax.random.PRNGKey(1), pts,
        ckm_mod.CKMConfig(k=3, m=60, decoder="sketch_shift", shift_steps=60,
                          shift_polish_steps=200, nnls_iters=80),
    )
    d = ckm_mod.diagnose(good, probe_budget=0.4)
    assert d.verdict == "ok" and d.ok

    # -- m too small: half-sketch decodes disagree --------------------------
    small = ckm_mod.fit(
        jax.random.PRNGKey(1), pts, ckm_mod.CKMConfig(**{**base, "m": 8})
    )
    d_m = ckm_mod.diagnose(small, probe_budget=0.4)
    assert d_m.verdict == "sketch_size"
    assert d_m.scores["subsketch_disagreement"] > 0.1

    # -- sigma mis-scaled, both directions ----------------------------------
    sig = float(good.sigma2)
    big = ckm_mod.fit(
        jax.random.PRNGKey(1), pts,
        ckm_mod.CKMConfig(**{**base, "sigma2": 1e4 * sig}),
    )
    d_big = ckm_mod.diagnose(big, probe_budget=0.4)
    assert d_big.verdict == "frequency_scale"
    assert d_big.scores["mean_modulus"] > 0.9
    assert "decrease" in d_big.recommendation

    tiny = ckm_mod.fit(
        jax.random.PRNGKey(1), pts,
        ckm_mod.CKMConfig(**{**base, "sigma2": 1e-4 * sig}),
    )
    d_tiny = ckm_mod.diagnose(tiny, probe_budget=0.4)
    assert d_tiny.verdict == "frequency_scale"
    assert d_tiny.scores["mean_modulus"] < 0.05
    assert "increase" in d_tiny.recommendation

    # -- decoder under-iterated: the probe finds a better fit ----------------
    lazy = ckm_mod.fit(
        jax.random.PRNGKey(1), pts,
        ckm_mod.CKMConfig(k=3, m=60, atom_steps=1, joint_steps=1,
                          nnls_iters=2, final_steps=0),
    )
    d_dec = ckm_mod.diagnose(lazy, probe_budget=0.4)
    assert d_dec.verdict == "decoder"
    assert (d_dec.scores["rel_residual"]
            > 1.5 * d_dec.scores["probe_rel_residual"])


@pytest.mark.slow
def test_diagnose_sigma_sweep_with_sample(blobs3):
    cfg = ckm_mod.CKMConfig(k=3, m=60, decoder="sketch_shift", shift_steps=60,
                            shift_polish_steps=200, nnls_iters=80)
    res = ckm_mod.fit(jax.random.PRNGKey(1), blobs3, cfg)
    d = ckm_mod.diagnose(res, probe_budget=0.3, sample=blobs3[:512])
    rows = d.details["sigma_sweep"]
    assert [r["factor"] for r in rows] == [0.1, 1.0, 10.0]
    # The fitted scale is the healthy one; the x10 scale pushes moduli up.
    assert rows[1]["healthy"]
    assert rows[2]["mean_modulus"] > rows[1]["mean_modulus"] > rows[0]["mean_modulus"]


def test_diagnose_emits_instruments(blobs3):
    cfg = ckm_mod.CKMConfig(k=3, m=60, decoder="sketch_shift", **FAST)
    res = ckm_mod.fit(jax.random.PRNGKey(1), blobs3, cfg)
    obs.enable()
    d = ckm_mod.diagnose(res, probe_budget=0.2)
    obs.disable()
    snap = obs.snapshot()
    assert snap[f"diagnose.verdicts{{verdict={d.verdict}}}"] == 1
    assert obs.TRACER.spans("ckm.diagnose")
