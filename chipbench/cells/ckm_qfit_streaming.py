"""Cell runner for ``kind: ckm_qfit_streaming``: back-to-back one-pass fits
with the 1-bit quantized sketch (QCKM).

The fits are ``ckm_fit_streaming``'s (same data, same keys, same spans)
with the configuration's ``sketch_quantization``: each point adds int32
codes of its dithered phases, and ``finalize`` dequantizes the code sums
before CLOMPR decodes.  An untraced window runs fits until ``--seconds``
have passed; a traced one runs exactly the traffic's ``traced_fits``, so
that every fit of the window is in the profiler's trace (it keeps device
ops for ~53 fits) and the run ends well inside its time.

Checked once the window has closed, against ``chipbench.reference`` (sigma^2,
frequencies, SSE) and ``chipbench.reference_qckm`` (dither, codes and their
dequantization), which derive everything from the data and each fit's key:
- ``sigma2_rel_err``, ``radius_cdf_err``, ``direction_err``: every fit, as
  in ``ckm_fit_streaming``;
- ``qsketch_rel_err``: the sketch of a sample of fits (drawn from the seed)
  against the reference's dequantized codes of all N points at the
  reference's frequencies and dither;
- ``bounds_abs_err``: the sampled fits' box bounds against the data's min
  and max (exact);
- ``sse_excess``: the median over the window's fits of SSE / SSE(true
  means), less 1.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import harness
from chipbench import reference as ref
from chipbench import reference_qckm as rq
from chipbench import traffic as gen
from chipbench.cells.ckm_fit_streaming import SPANS, annotated

DEQUANTIZE = "engine.dequantize"


def run(ctx: harness.RunContext) -> None:
    import jax

    from repro.core import ckm

    cfg, tr = ctx.cell.config, ctx.cell.traffic

    ctx.phase("import the program")
    key = harness.seed_key(ctx.seed)
    k_data, k_fit = jax.random.split(key)
    chunks, means = gen.mixture_chunks(k_data, cfg["points"], cfg["chunk"],
                                       cfg["k"], cfg["n"], cfg["mixture_c"])
    jax.block_until_ready(chunks)
    ctx.phase(f"draw {cfg['points']} points on the device")
    ckm_cfg = ckm.CKMConfig(k=cfg["k"], m=cfg["m"],
                            sketch_backend=cfg["sketch_backend"],
                            decoder=cfg["decoder"],
                            sketch_quantization=cfg["sketch_quantization"])

    def fit(i):
        with jax.profiler.TraceAnnotation(SPANS[0]):
            res = ckm.fit_streaming(jax.random.fold_in(k_fit, i),
                                    annotated(chunks), ckm_cfg)
            jax.block_until_ready(res.centroids)
        return res

    warm = int(tr["warmup_fits"])
    for i in range(warm):
        fit(i)
    ctx.phase(f"{warm} warm-up fit(s)")
    ctx.setup_s = time.perf_counter() - harness.T_PROCESS

    # -- the measured window ------------------------------------------------
    results = []
    before = ctx.counter.snapshot()
    if ctx.trace:
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=harness.profile_options())
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        i = warm
        while True:
            results.append(fit(i))
            i += 1
            if ctx.trace:
                if len(results) >= int(tr["traced_fits"]):
                    break
            elif time.perf_counter() - t0 >= ctx.seconds:
                break
    window = time.perf_counter() - t0
    if ctx.trace:
        jax.profiler.stop_trace()
        harness.log(f"[trace] stop_trace: {time.perf_counter() - t0 - window:.3f} s")
    after = ctx.counter.snapshot()
    ctx.window_compiles = {k: after[k] - before[k] for k in after}
    ctx.memory_peak_bytes = harness.memory_peak_bytes(1)

    fits = len(results)
    ctx.attempted = fits
    ctx.end_to_end["fit_s"] = window / fits
    ctx.counts = {"fits": fits, "window_s": window,
                  "points_per_fit": cfg["points"],
                  "kernel_calls_per_fit": cfg["points"] // cfg["chunk"]}

    # -- the comparison with the reference ------------------------------------
    t_check = time.perf_counter()
    answers = [(np.asarray(r.sketch), np.asarray(r.freq_op.materialize()),
                float(r.sigma2), np.asarray(r.bounds[0]), np.asarray(r.bounds[1]),
                np.asarray(r.centroids)) for r in results]
    del results
    law, est = cfg["frequencies"], cfg["sigma2_estimate"]
    head = np.asarray(chunks[0][: est["sample"]])
    s_err, cdf_err, dir_err, w_ref = [], [], [], []
    for i, (_, w, s2, *_) in enumerate(answers):
        k_sig, k_freq = ref.sketch_keys(jax.random.fold_in(k_fit, warm + i))
        s2_ref = ref.estimate_sigma2(k_sig, head, law, est)
        s_err.append(abs(s2 - s2_ref) / s2_ref)
        gaps = ref.frequency_errors(w, k_freq, s2, law)
        cdf_err.append(gaps[0])
        dir_err.append(gaps[1])
        w_ref.append(gaps[2])
    rng = np.random.default_rng([ctx.seed, 1])
    sample = sorted(rng.choice(fits, size=min(fits, int(tr["checked_fits"])),
                               replace=False))
    z_err, b_err = [], []
    for j in sample:
        z, _, _, lo, hi, _ = answers[j]
        xi = rq.dither(jax.random.fold_in(k_fit, warm + j), cfg["m"])
        z_ref, lo_ref, hi_ref = rq.sketch(chunks, w_ref[j], xi)
        z_err.append(ref.rel_err(z, z_ref))
        b_err.append(float(max(np.max(np.abs(lo - np.asarray(lo_ref))),
                               np.max(np.abs(hi - np.asarray(hi_ref))))))
    sse_true = ref.sse(chunks, means)
    ratios = [ref.sse(chunks, a[5]) / sse_true for a in answers]
    harness.log(f"[check] sigma^2 errors {s_err}; radius CDF gaps {cdf_err}; "
                f"direction gaps {dir_err}")
    harness.log(f"[check] fits checked against the reference's dequantized "
                f"codes: {sample}; sketch errors {z_err}; sse ratios {ratios}")
    lim = cfg["limits"]
    ctx.checks = [
        harness.Check("sigma2_rel_err", max(s_err), lim["sigma2_rel_err"]),
        harness.Check("radius_cdf_err", max(cdf_err), lim["radius_cdf_err"]),
        harness.Check("direction_err", max(dir_err), lim["direction_err"]),
        harness.Check("qsketch_rel_err", max(z_err), lim["qsketch_rel_err"]),
        harness.Check("bounds_abs_err", max(b_err), lim["bounds_abs_err"]),
        harness.Check("sse_excess", float(np.median(ratios)) - 1.0,
                      lim["sse_excess"]),
    ]
    per_fit = {"sigma2_rel_err": s_err, "radius_cdf_err": cdf_err,
               "direction_err": dir_err}
    bad = {i for name, errs in per_fit.items() for i, e in enumerate(errs)
           if not e <= lim[name]}
    bad |= {j for j, ze, be in zip(sample, z_err, b_err)
            if not (ze <= lim["qsketch_rel_err"] and be <= lim["bounds_abs_err"])}
    ctx.failed = len(bad)
    harness.log(f"[check] reference comparison: {time.perf_counter() - t_check:.3f} s")
    if ctx.trace:
        from chipbench import trace_reduce

        t_read = time.perf_counter()
        ctx.trace_data = trace_reduce.load_xplane(ctx.trace_dir, SPANS + (DEQUANTIZE,))
        harness.log(f"[trace] read: {time.perf_counter() - t_read:.3f} s")
