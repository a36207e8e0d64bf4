"""The 1-bit quantized fit cell (``qfit_paper_1bit``) on the CPU: the
program's codes and dequantized sketch against ``chipbench.reference_qckm``,
a tiny rehearsal of the cell, its planted faults, and the span and counter
the quantized finalize and update carry."""

import json

import jax
import numpy as np
import pytest

import tiny
from chipbench import faults_qckm, harness
from chipbench import reference as ref
from chipbench import reference_qckm as rq
from chipbench import traffic

# The cell's own sizes, cut to the CPU in this test's copy of the benchmark.
TINY_QCKM = {"points": 8192, "chunk": 2048, "n": 3, "k": 2, "m": 60}
TINY_TRAFFIC = {"checked_fits": 2, "traced_fits": 2}


def _config():
    return json.loads(
        (tiny.REPO / "chipbench" / "configs" / "qckm_paper_1e7_1bit.json").read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tiny.tiny_root(tmp_path_factory.mktemp("bench"))
    for path, sizes in (
            (r / "chipbench" / "configs" / "qckm_paper_1e7_1bit.json", TINY_QCKM),
            (r / "chipbench" / "traffic" / "fit_repeat_traced30.json", TINY_TRAFFIC)):
        d = json.loads(path.read_text())
        d.update(sizes)
        path.write_text(json.dumps(d))
    yield r
    tiny.restore_jax_cache_config()


def _draw(seed, points, chunk):
    cfg = _config()
    chunks, _ = traffic.mixture_chunks(harness.seed_key(seed), points, chunk,
                                       cfg["k"], cfg["n"], cfg["mixture_c"])
    return cfg, chunks


# Phases that round apart by an ulp flip a code only within ~1e-6 rad of a
# sign boundary: ~2e-8 of the codes (6 of 2.6e8 at n = 10, m = 1000).
MAX_FLIPS = 4


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_code_sums_match_reference(backend):
    """At the configured widths (n = 10, m = 1000) the program's 1-bit code
    sums equal the reference's, but for at most a handful of boundary flips
    (each moves a sum by 2)."""
    from repro.core import ckm
    from repro.core.sketch import sketch_quantized
    from repro.kernels import ops

    cfg, chunks = _draw(7, 4096, 4096)
    fit_key = jax.random.fold_in(harness.seed_key(8), 2)
    res = ckm.compute_sketch_streaming(
        jax.random.split(fit_key)[0], chunks,
        ckm.CKMConfig(k=cfg["k"], m=cfg["m"], sketch_quantization="1bit"))
    op = res[1]
    xi = rq.dither(fit_key, cfg["m"])
    if backend == "xla":
        q_c, q_s = sketch_quantized(chunks[0], op, xi)
    else:
        q_c, q_s = ops.quantized_fourier_sketch_sums(chunks[0], op, xi, bits=1)
    r_c, r_s = rq.code_sums(chunks, op.materialize(), xi)
    flips = (np.abs(np.asarray(q_c, np.int64) - r_c).sum()
             + np.abs(np.asarray(q_s, np.int64) - r_s).sum()) // 2
    assert flips <= MAX_FLIPS


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_dequantized_sketch_within_limit(seed):
    """The program's dequantized sketch against the reference's dequantized
    codes at the reference's own frequencies and dither, at the configured
    widths: well inside ``qsketch_rel_err``; and the one-pass bf16 control
    outside it."""
    from repro.core import ckm

    cfg, chunks = _draw(seed, 2**15, 2**13)
    law, lim = cfg["frequencies"], cfg["limits"]
    fit_key = jax.random.fold_in(harness.seed_key(seed + 1), 4)
    z, op, s2, _, _ = ckm.compute_sketch_streaming(
        jax.random.split(fit_key)[0], chunks,
        ckm.CKMConfig(k=cfg["k"], m=cfg["m"], sketch_quantization="1bit"))
    _, k_freq = ref.sketch_keys(fit_key)
    w_ref = ref.frequency_errors(op.materialize(), k_freq, float(s2), law)[2]
    xi = rq.dither(fit_key, cfg["m"])
    z_ref = rq.sketch(chunks, w_ref, xi)[0]
    assert ref.rel_err(z, z_ref) < lim["qsketch_rel_err"] / 10
    z_ctl = rq.sketch(chunks, w_ref, xi, "bf16")[0]
    assert ref.rel_err(z_ctl, z_ref) > lim["qsketch_rel_err"]


def test_dequantize_scales_and_rotates_back():
    """The reference's dequantization of the codes of n copies of one point:
    the code pair is the centre of the dithered phase's quadrant; pi/4 scales
    it, and the rotation by -xi takes the dither off its angle."""
    xi = np.array([0.0, 1.0, 4.0])
    theta = np.array([0.3, 1.9, -2.4]) + xi
    n = 1000
    s_c, s_s = np.where(np.cos(theta) >= 0, 1, -1), np.where(np.sin(theta) >= 0, 1, -1)
    z = rq.dequantize(n * s_c, n * s_s, xi, n)
    angle = np.arctan2(-z[3:], z[:3])  # z = [sum cos, -sum sin] / n
    gap = angle - (np.arctan2(s_s, s_c) - xi)
    assert np.allclose(np.angle(np.exp(1j * gap)), 0.0, atol=1e-12)
    assert np.allclose(np.hypot(z[:3], z[3:]), np.pi / 4 * np.sqrt(2))


def test_cell_rehearsal(root):
    r = harness.run_cell("qfit_paper_1bit", 2**31 + 5, 0.5, False, root=root,
                         require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"fit_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["checks"]) == {"sigma2_rel_err", "radius_cdf_err", "direction_err",
                                "qsketch_rel_err", "bounds_abs_err", "sse_excess"}
    assert list(r)[-1] == "checks"


def test_traced_rehearsal_times_the_traced_fits(root):
    r = harness.run_cell("qfit_paper_1bit", 3, 0.0, True, root=root,
                         require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] == TINY_TRAFFIC["traced_fits"]
    # On the CPU there is no device plane: every new reader finds nothing.
    assert r["metrics"] == {}
    assert r["device"]["window_s"] > 0


# The number each planted fault has to fail at CPU size.
CATCHES = {
    "control": "qsketch_rel_err",
    "state_unchanged": "qsketch_rel_err",
    "answer_altered": "sse_excess",
    "dither_dropped": "qsketch_rel_err",
    "float_sketch": "qsketch_rel_err",
}


@pytest.mark.parametrize("fault", faults_qckm.FAULTS)
def test_planted_fault_is_not_correct(root, fault):
    with faults_qckm.planted(fault):
        r = harness.run_cell("qfit_paper_1bit", 21, 0.3, False, root=root,
                             require_tpu=False)
    assert not r["correct"], r["checks"]
    c = r["checks"][CATCHES[fault]]
    assert not c["value"] <= c["limit"], r["checks"]


def _stream_fit(quantization):
    from repro.core import ckm

    cfg, chunks = _draw(5, 3000, 1000)
    ckm.fit_streaming(jax.random.PRNGKey(1), chunks,
                      ckm.CKMConfig(k=2, m=40, sketch_quantization=quantization,
                                    atom_steps=5, joint_steps=5, final_steps=5,
                                    nnls_iters=5))
    return sum(int(c.shape[0]) for c in chunks)


@pytest.mark.parametrize("quantization", ["1bit", "none"])
def test_dequantize_span_once_per_quantized_finalize(quantization):
    from repro import obs

    obs.TRACER.reset()
    with obs.runtime.enabled_scope():
        _stream_fit(quantization)
    spans = obs.TRACER.spans("engine.dequantize")
    obs.TRACER.reset()
    obs.metrics.reset()
    if quantization == "none":
        assert spans == []
    else:
        assert len(spans) == 1
        assert spans[0]["attrs"] == {"bits": 1}
        assert spans[0]["parent"] == "engine.finalize"


def test_update_rows_counts_every_quantized_row():
    from repro import obs

    obs.metrics.reset()
    with obs.runtime.enabled_scope():
        rows = _stream_fit("1bit")
    snap = obs.snapshot()
    obs.TRACER.reset()
    obs.metrics.reset()
    assert snap["engine.update.rows{backend=xla,bits=1}"] == rows
    assert "engine.update.rows{backend=xla,bits=none}" not in snap


def test_work_counts_the_kernel_shapes():
    from chipbench import qckm_work

    flops, nbytes = qckm_work.qsketch_kernel_work(10**7, 10, 1000, 10)
    assert flops == 2e11
    assert nbytes == 4 * 10**7 * 10 + 4 * 10**7 + 10 * (4 * (10 * 1000 + 1000) + 8 * 1000)
