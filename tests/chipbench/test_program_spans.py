"""The readers of the program's own spans (``chipbench.program_spans``), on a
hand-checked trace of two fits, and the reading of those spans and their
stats back from a profile recorded on the CPU."""

import importlib.util
import types

import pytest

import tiny
from chipbench import program_spans as ps
from chipbench import trace_reduce as tr

METRICS = ("compile_host_ms.fit", "decode_idle_ms.fit",
           "sketch_setup_idle_ms.fit", "ingest_idle_ms.fit")


def _reader(name):
    path = tiny.REPO / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ev(name, start, end, dev=0):
    return tr.Event(name, float(start), float(end - start), dev)


def _sp(name, start, end, **stats):
    return ps.Span(name, float(start), float(end - start), stats)


# Window [0, 1000] ns holding two fits; a warm-up fit before it must not
# count.  Device ops [120, 240], [400, 470], [620, 720], [900, 990]; idle
# gaps [0, 120], [240, 400], [470, 620], [720, 900], [990, 1000].
HAND_SPANS = [
    _sp("ckm.fit", -500, -20, trace_ms=1000.0, lower_ms=0.0, compile_ms=0.0,
        jax_compiles=9),
    _sp("ckm.fit", 0, 480, trace_ms=5.0, lower_ms=1.0, compile_ms=2.0,
        jax_compiles=1),
    _sp("ckm.sigma2", 10, 60, trace_ms=10.0, lower_ms=0.0, compile_ms=0.0,
        jax_compiles=0),
    _sp("ckm.operator", 60, 90, trace_ms=4.0, lower_ms=0.0, compile_ms=0.0,
        jax_compiles=0),
    _sp("ckm.operator", 90, 100, trace_ms=0.0, lower_ms=0.0, compile_ms=0.0,
        jax_compiles=0),
    _sp("ckm.ingest", 100, 150, chunk=0, trace_ms=0.0, lower_ms=0.0,
        compile_ms=0.0, jax_compiles=0),
    _sp("ckm.ingest", 150, 250, chunk=1, trace_ms=0.0, lower_ms=0.0,
        compile_ms=0.0, jax_compiles=0),
    _sp("ckm.decode", 260, 460, trace_ms=100.0, lower_ms=20.0,
        compile_ms=30.0, jax_compiles=1),
    _sp("ckm.fit", 500, 980, trace_ms=3.0, lower_ms=1.0, compile_ms=0.0,
        jax_compiles=0),
    _sp("ckm.sigma2", 510, 560, trace_ms=8.0, lower_ms=0.0, compile_ms=0.0,
        jax_compiles=0),
    _sp("ckm.operator", 560, 600, trace_ms=2.0, lower_ms=0.0,
        compile_ms=0.0, jax_compiles=0),
    _sp("ckm.ingest", 600, 700, chunk=0, trace_ms=0.0, lower_ms=0.0,
        compile_ms=0.0, jax_compiles=0),
    _sp("ckm.decode", 710, 960, trace_ms=90.0, lower_ms=15.0,
        compile_ms=20.0, jax_compiles=1),
]


def _ctx(ops, fits=2):
    data = tr.TraceData(
        ops=ops, modules=[],
        spans=[_ev(tr.WINDOW_SPAN, 0, 1000), _ev("ckm.fit_streaming", 0, 490),
               _ev("ckm.fit_streaming", 495, 990)],
        n_devices=1)
    return types.SimpleNamespace(trace_data=data, trace_dir="unused",
                                 counts={"fits": fits})


HAND_OPS = [_ev("fourier_sketch_kernel.1", 120, 240), _ev("while.1", 400, 470),
            _ev("fourier_sketch_kernel.1", 620, 720), _ev("while.1", 900, 990)]


@pytest.fixture
def hand(monkeypatch):
    monkeypatch.setattr(ps, "load_spans", lambda log_dir: list(HAND_SPANS))
    return _ctx(HAND_OPS)


def test_idle_inside_the_program_spans(hand):
    # ckm.decode: [260, 400] of fit 1 and [720, 900] of fit 2 idle
    # (140 + 180 ns), over 2 fits, in ms.
    assert _reader("decode_idle_ms.fit")(hand, "TPU v5 lite") \
        == pytest.approx(160e-6)
    # ckm.sigma2 + ckm.operator: [10, 100] and [510, 600], idle 90 + 90 ns.
    assert _reader("sketch_setup_idle_ms.fit")(hand, "TPU v5 lite") \
        == pytest.approx(90e-6)
    # ckm.ingest: [100, 120] + [240, 250] + [600, 620] = 50 ns.
    assert _reader("ingest_idle_ms.fit")(hand, "TPU v5 lite") \
        == pytest.approx(25e-6)


def test_compile_stats_in_the_window(hand):
    # Fit 1: 8 + 10 + 4 + 150; fit 2: 4 + 8 + 2 + 125 ms; the warm-up fit
    # before the window is left out.
    assert _reader("compile_host_ms.fit")(hand, "TPU v5 lite") \
        == pytest.approx((172.0 + 139.0) / 2)


def test_the_spans_account_for_the_idle_time(hand):
    got = ps.spans(hand)
    assert len(got) == len(HAND_SPANS) - 1
    fit = [s for s in got if s.name == "ckm.fit"]
    kids = ps.inside(got, fit)
    assert len(kids) == len(got) - len(fit)
    # Self idle of ckm.fit: [0, 10], [250, 260], [470, 480] of fit 1 and
    # [500, 510] of fit 2.
    assert ps.idle_ns(hand, fit, kids) == pytest.approx(40)
    children = sum(ps.idle_ns(hand, [s for s in got if s.name in names])
                   for names in (("ckm.decode",), ("ckm.ingest",),
                                 ("ckm.sigma2", "ckm.operator")))
    assert children == pytest.approx(320 + 50 + 180)
    # The benchmark's own spans are charged each gap by its midpoint, so
    # [470, 620] goes whole to the second fit; of the 610 ns, only the
    # 20 ns between the program's two fits ([480, 500]) lie in no
    # program span.
    lo, hi = hand.trace_data.window()
    charged = tr.attribute_gaps(hand.trace_data, lo, hi)
    assert 1e9 * charged["ckm.fit_streaming"] == pytest.approx(610)
    assert children + ps.idle_ns(hand, fit, kids) == pytest.approx(610 - 20)


def test_no_device_plane_no_reading(monkeypatch):
    monkeypatch.setattr(ps, "load_spans", lambda log_dir: list(HAND_SPANS))
    ctx = _ctx(ops=[])
    assert all(_reader(m)(ctx, "cpu") is None for m in METRICS)


def test_a_program_without_the_spans_gives_no_reading(monkeypatch):
    monkeypatch.setattr(ps, "load_spans", lambda log_dir: [])
    ctx = _ctx(HAND_OPS)
    assert all(_reader(m)(ctx, "TPU v5 lite") is None for m in METRICS)


def test_spans_and_stats_read_back_from_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    import repro.obs as obs

    step = jax.jit(lambda v: v * 3.0 + 1.0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with obs.span("ckm.fit"):
            with obs.span("ckm.ingest", chunk=3):
                step(jnp.ones(5)).block_until_ready()
            with obs.span("ckm.decode", decoder="clompr"):
                pass
    got = ps.load_spans(str(tmp_path))
    assert [s.name for s in got] == ["ckm.fit", "ckm.ingest", "ckm.decode"]
    fit, ingest, decode = got
    assert ingest.stats["chunk"] == 3 and decode.stats["decoder"] == "clompr"
    assert ingest.stats["req"] == fit.stats["req"] == decode.stats["req"]
    assert ingest.stats["trace_ms"] > 0 and ingest.stats["jax_compiles"] >= 1
    assert fit.stats["trace_ms"] == 0 and decode.stats["compile_ms"] == 0
    assert ps.inside(got, [fit]) == [ingest, decode]
