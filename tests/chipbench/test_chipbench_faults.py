"""The comparison that decides ``correct`` fails when it should, and holds
when it should.

The cell is driven at CPU size with the timed path broken underneath (the
harness's look for a chip skipped): the control (the reference at one bf16
pass in the program's place), a step that returns its state unchanged, half
of each batch left out with the mean taken over the rest, an answer
altered where it is produced, a doubled frequency scale and a wrong radius
law.  A one-chip cell has no exchange between chips to leave out.
"""

import numpy as np
import pytest

import tiny
from chipbench import faults, harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    yield tiny.tiny_root(tmp_path_factory.mktemp("bench"))
    tiny.restore_jax_cache_config()


# The number each planted fault has to fail at CPU size.
CATCHES = {
    "control": "sketch_rel_err",
    "state_unchanged": "sketch_rel_err",
    "half_batch": "sketch_rel_err",
    "answer_altered": "sse_excess",
    "sigma2_scaled": "sigma2_rel_err",
    "radius_law": "radius_cdf_err",
}


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(root, fault):
    with faults.planted("ckm_fit_streaming", fault):
        r = harness.run_cell("fit_paper", 21, 0.3, False, root=root,
                             require_tpu=False)
    assert not r["correct"], r["checks"]
    c = r["checks"][CATCHES[fault]]
    assert not c["value"] <= c["limit"], r["checks"]


def _config(name):
    import json

    return json.loads((tiny.REPO / "chipbench" / "configs" / f"{name}.json").read_text())


def test_control_fails_the_fit_sketch_at_the_configured_widths():
    """The control (the reference at one bf16 pass) against the reference, on
    2^17 of the paper mixture's points at n = 10, m = 1000 with the
    frequencies the program draws for them: it must read above the cell's
    limit."""
    import jax

    from chipbench import reference as ref
    from chipbench import traffic
    from repro.core import ckm

    cfg = _config("ckm_paper_1e7")
    chunks, _ = traffic.mixture_chunks(harness.seed_key(5), 2**17, 2**15,
                                       cfg["k"], cfg["n"], cfg["mixture_c"])
    res = ckm.compute_sketch(jax.random.PRNGKey(5), chunks[0],
                             ckm.CKMConfig(k=cfg["k"], m=cfg["m"]))
    w = res[1].materialize()
    z_ref = ref.sketch(chunks, w)[0]
    z_ctl = ref.sketch(chunks, w, "bf16")[0]
    assert ref.rel_err(z_ctl, z_ref) > cfg["limits"]["sketch_rel_err"]


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_reference_derives_the_programs_scale_and_frequencies(seed):
    """At the configured widths (n = 10, m = 1000) the reference's own
    sigma^2 estimate and its own draw match the program's, well inside
    the cell's limits, and the sketch at the reference's frequencies
    matches the sketch at the program's."""
    import jax

    from chipbench import reference as ref
    from chipbench import traffic
    from repro.core import ckm

    cfg = _config("ckm_paper_1e7")
    law, est, lim = cfg["frequencies"], cfg["sigma2_estimate"], cfg["limits"]
    chunks, _ = traffic.mixture_chunks(harness.seed_key(seed), 2**15, 2**13,
                                       cfg["k"], cfg["n"], cfg["mixture_c"])
    fit_key = jax.random.fold_in(harness.seed_key(seed + 1), 4)
    res = ckm.compute_sketch_streaming(jax.random.split(fit_key)[0], chunks,
                                       ckm.CKMConfig(k=cfg["k"], m=cfg["m"]))
    z, op, s2 = res[0], res[1], float(res[2])
    k_sig, k_freq = ref.sketch_keys(fit_key)
    s2_ref = ref.estimate_sigma2(k_sig, np.asarray(chunks[0]), law, est)
    assert abs(s2 - s2_ref) / s2_ref < lim["sigma2_rel_err"] / 100
    cdf_gap, dir_gap, w_ref = ref.frequency_errors(op.materialize(), k_freq, s2, law)
    assert cdf_gap < lim["radius_cdf_err"] / 10
    assert dir_gap < lim["direction_err"] / 10
    assert ref.rel_err(z, ref.sketch(chunks, w_ref)[0]) < lim["sketch_rel_err"] / 10
