"""The trace reduction and the roofline count, on hand-checked inputs and on
a small trace recorded on a TPU v5e (one 32-request fleet flush and one
decode)."""

import json
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts the repo on sys.path)
from chipbench import peaks
from chipbench import trace_reduce as tr

FIXTURE = Path(__file__).with_name("data") / "fixture_trace.json"


def _ev(name, start, dur, dev=0):
    return tr.Event(name, float(start), float(dur), dev)


@pytest.fixture
def hand_trace():
    # Window [0, 100] ns.  Device ops: [10, 30] and [20, 40] overlap,
    # [60, 70] is a kernel, [95, 120] runs past the window's end.
    return tr.TraceData(
        ops=[_ev("fusion.1", 10, 20), _ev("fusion.2", 20, 20),
             _ev("fourier_sketch_kernel.1", 60, 10), _ev("copy.3", 95, 25)],
        modules=[_ev("jit_update", 10, 30), _ev("jit_clompr", 60, 10)],
        spans=[_ev(tr.WINDOW_SPAN, 0, 100), _ev("outer", 0, 100),
               _ev("inner", 40, 20)],
        n_devices=1,
    )


def test_busy_is_the_union_of_op_intervals_in_the_window(hand_trace):
    # [10, 40] + [60, 70] + [95, 100] = 30 + 10 + 5
    assert tr.busy_ns(hand_trace, 0, 100) == 45
    assert tr.busy_ns(hand_trace, 15, 65) == 25 + 5


def test_idle_gaps_go_to_the_innermost_open_span(hand_trace):
    assert tr.idle_gaps(hand_trace, 0, 100) == [(0, 10), (40, 60), (70, 95)]
    gaps = tr.attribute_gaps(hand_trace, 0, 100)
    assert gaps == pytest.approx({"outer": 35e-9, "inner": 20e-9})


def test_kernel_and_module_time(hand_trace):
    assert tr.op_seconds(hand_trace, 0, 100,
                         lambda n: "fourier_sketch" in n) == pytest.approx(10e-9)
    assert tr.module_seconds(hand_trace, 0, 100,
                             lambda n: n.startswith("jit_clompr")) == pytest.approx(10e-9)
    top = tr.top_ops(hand_trace, 0, 100)
    assert top[0] == ["fusion.1", pytest.approx(20e-9)]


def test_names_from_the_profiler():
    assert tr.op_name("%fourier_sketch_kernel.1 = (f32[1,1000]) custom-call(x)") \
        == "fourier_sketch_kernel.1"
    assert tr.module_name("jit_clompr(8154332351223987289)") == "jit_clompr"


def test_recorded_chip_trace():
    t = tr.TraceData.from_json(json.loads(FIXTURE.read_text()))
    lo, hi = t.window()
    busy = tr.busy_ns(t, lo, hi)
    assert 0 < busy < hi - lo
    # The union never exceeds the summed op time, and every idle
    # nanosecond of the window is charged to some name.
    assert busy <= sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in t.ops
                       if e.end_ns > lo and e.start_ns < hi)
    gaps = tr.attribute_gaps(t, lo, hi)
    assert sum(gaps.values()) == pytest.approx((hi - lo - busy) * 1e-9, rel=1e-9)
    assert {"bench.flush", "bench.decode"} <= set(gaps)
    kernel = tr.op_seconds(t, lo, hi, lambda n: "fourier_sketch" in n)
    assert kernel > 0
    assert tr.module_seconds(t, lo, hi, lambda n: n.startswith("jit_sketch_shift")) > 0
    b = tr.breakdown(t, lo, hi)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10


def test_roofline_count_at_the_paper_sizes():
    # 10^7 points, n = 10, m = 1000, in 10 calls: 2e11 FLOP; the points are
    # 4e8 bytes, plus 10 x (W 4e4 + sums 8e3) bytes.
    flops, nbytes = peaks.sketch_kernel_work(10**7, 10, 1000, 10)
    assert flops == 2e11
    assert nbytes == 4e8 + 10 * (4e4 + 8e3)
    # At 0.3 s of kernel time: 2e11 / 197e12 = 1.015 ms, compute-bound.
    share, bound = peaks.roofline_share(flops, nbytes, 0.3, "TPU v5 lite")
    assert bound == "compute"
    assert share == pytest.approx(100 * (2e11 / 197e12) / 0.3)
    # A memory-bound count: 1e9 bytes, 1 FLOP.
    share, bound = peaks.roofline_share(1.0, 819e9, 2.0, "TPU v5 lite")
    assert (share, bound) == (pytest.approx(50.0), "memory")


def test_unknown_device_has_no_peak():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("cpu")
