"""Behaviour tests for the CKM decoder + Lloyd baseline (paper §3.2, §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ckm as ckm_mod
from repro.core import lloyd as lloyd_mod
from repro.core import nnls as nnls_mod
from repro.data import synthetic


def _match_errors(truth, cents):
    d = np.linalg.norm(np.asarray(truth)[:, None] - np.asarray(cents)[None], axis=-1)
    errs = []
    d = d.copy()
    for _ in range(truth.shape[0]):
        i, j = np.unravel_index(np.argmin(d), d.shape)
        errs.append(d[i, j])
        d[i, :] = np.inf
        d[:, j] = np.inf
    return np.array(errs)


@pytest.mark.slow
class TestCKMRecovery:
    def test_recovers_separated_clusters(self, gaussian_blobs):
        """On well-separated blobs CKM must localise every true mean."""
        x, labels, means = gaussian_blobs
        cfg = ckm_mod.CKMConfig(k=5)
        res = ckm_mod.fit(jax.random.PRNGKey(0), x, cfg)
        errs = _match_errors(means, res.centroids)
        assert np.all(errs < 1.0), errs  # within a cluster std of each mean

    def test_weights_are_probabilities(self, gaussian_blobs):
        x, _, _ = gaussian_blobs
        res = ckm_mod.fit(jax.random.PRNGKey(1), x, ckm_mod.CKMConfig(k=5))
        w = np.asarray(res.weights)
        assert np.all(w >= 0) and abs(w.sum() - 1.0) < 1e-5

    def test_sse_close_to_lloyd(self, gaussian_blobs):
        """Paper's headline: CKM SSE comparable to Lloyd-Max (rel < 1.5).

        Best-of-3 on both sides: single-replicate CKM is at the mercy of the
        frequency draw (~1-in-7 seeds miss a cluster), and the paper's own
        protocol is best-of-replicates — mirror the Lloyd baseline below."""
        x, _, _ = gaussian_blobs
        res = ckm_mod.fit(
            jax.random.PRNGKey(2), x, ckm_mod.CKMConfig(k=5, replicates=3)
        )
        km = lloyd_mod.kmeans(
            jax.random.PRNGKey(3), x, lloyd_mod.LloydConfig(k=5, replicates=3)
        )
        rel = float(ckm_mod.sse(x, res.centroids)) / float(km.sse)
        assert rel < 1.5, rel

    def test_replicates_select_lower_cost(self, gaussian_blobs):
        x, _, _ = gaussian_blobs
        r1 = ckm_mod.fit(jax.random.PRNGKey(4), x, ckm_mod.CKMConfig(k=5))
        r3 = ckm_mod.fit(
            jax.random.PRNGKey(4), x, ckm_mod.CKMConfig(k=5, replicates=3)
        )
        assert float(r3.cost) <= float(r1.cost) + 1e-6

    def test_init_strategies_run(self, gaussian_blobs):
        """range / sample / kpp all produce valid centroids (paper §4.2)."""
        x, _, means = gaussian_blobs
        for init in ("range", "sample", "kpp"):
            cfg = ckm_mod.CKMConfig(k=5, init=init, atom_steps=100, joint_steps=80)
            res = ckm_mod.fit(jax.random.PRNGKey(5), x, cfg)
            assert res.centroids.shape == (5, 4)
            assert np.all(np.isfinite(np.asarray(res.centroids)))

    def test_centroids_respect_bounds(self, gaussian_blobs):
        """Box constraint l <= c <= u (paper's 'additional constraints')."""
        x, _, _ = gaussian_blobs
        res = ckm_mod.fit(jax.random.PRNGKey(6), x, ckm_mod.CKMConfig(k=5))
        lo, hi = res.bounds
        c = res.centroids
        assert bool(jnp.all(c >= lo - 1e-5)) and bool(jnp.all(c <= hi + 1e-5))

    def test_decode_from_sketch_only(self, gaussian_blobs):
        """Compressive contract: decoding uses only (z, W, l, u) — no data."""
        x, _, means = gaussian_blobs
        cfg = ckm_mod.CKMConfig(k=5)
        z, w, _, (lo, hi) = ckm_mod.compute_sketch(jax.random.PRNGKey(7), x, cfg)
        cents, alphas, cost = ckm_mod.decode_sketch(
            jax.random.PRNGKey(8), z, w, lo, hi, cfg
        )
        errs = _match_errors(means, cents)
        assert np.all(errs < 1.2), errs


@pytest.mark.slow
class TestLloyd:
    def test_recovers_separated_clusters(self, gaussian_blobs):
        x, _, means = gaussian_blobs
        res = lloyd_mod.kmeans(
            jax.random.PRNGKey(0), x, lloyd_mod.LloydConfig(k=5, replicates=3, init="kpp")
        )
        errs = _match_errors(means, res.centroids)
        assert np.all(errs < 0.5), errs

    def test_sse_decreases_with_replicates(self, gaussian_blobs):
        x, _, _ = gaussian_blobs
        r1 = lloyd_mod.kmeans(jax.random.PRNGKey(1), x, lloyd_mod.LloydConfig(k=5))
        r5 = lloyd_mod.kmeans(
            jax.random.PRNGKey(1), x, lloyd_mod.LloydConfig(k=5, replicates=5)
        )
        assert float(r5.sse) <= float(r1.sse) * (1.0 + 1e-5)

    def test_kpp_beats_range_on_average(self, gaussian_blobs):
        """k-means++ should not be worse than range init (paper Fig. 1)."""
        x, _, _ = gaussian_blobs
        sses = {}
        for init in ("range", "kpp"):
            vals = [
                float(
                    lloyd_mod.lloyd(
                        jax.random.PRNGKey(s), x, lloyd_mod.LloydConfig(k=5, init=init)
                    ).sse
                )
                for s in range(5)
            ]
            sses[init] = np.mean(vals)
        assert sses["kpp"] <= sses["range"] * 1.05


class TestNNLS:
    def test_matches_scipy(self):
        from scipy.optimize import nnls as scipy_nnls

        rng = np.random.default_rng(0)
        a = rng.normal(size=(40, 8)).astype(np.float32)
        beta_true = np.abs(rng.normal(size=8)).astype(np.float32)
        beta_true[2] = 0.0
        z = a @ beta_true
        mask = jnp.ones((8,), bool)
        beta = nnls_mod.nnls(jnp.asarray(a), jnp.asarray(z), mask, iters=500)
        ref, _ = scipy_nnls(a, z)
        np.testing.assert_allclose(np.asarray(beta), ref, atol=2e-3)

    def test_mask_pins_columns(self):
        rng = np.random.default_rng(1)
        a = rng.normal(size=(20, 6)).astype(np.float32)
        z = rng.normal(size=20).astype(np.float32)
        mask = jnp.asarray([True, False, True, True, False, True])
        beta = nnls_mod.nnls(jnp.asarray(a), jnp.asarray(z), mask)
        assert float(beta[1]) == 0.0 and float(beta[4]) == 0.0

    def test_nonnegative(self):
        rng = np.random.default_rng(2)
        a = rng.normal(size=(30, 5)).astype(np.float32)
        z = rng.normal(size=30).astype(np.float32)
        beta = nnls_mod.nnls(jnp.asarray(a), jnp.asarray(z), jnp.ones((5,), bool))
        assert np.all(np.asarray(beta) >= 0)

    def test_empty_support_returns_zero(self):
        """Regression (PR 6): with every column masked the gram matrix is 0,
        the power-iteration Rayleigh quotient hits its floor, and the old
        1/(2*1e-12) step produced inf/NaN iterates.  The answer is beta = 0."""
        rng = np.random.default_rng(3)
        a = rng.normal(size=(20, 6)).astype(np.float32)
        z = rng.normal(size=20).astype(np.float32)
        beta = nnls_mod.nnls(
            jnp.asarray(a), jnp.asarray(z), jnp.zeros((6,), bool)
        )
        np.testing.assert_array_equal(np.asarray(beta), np.zeros(6, np.float32))

    def test_nan_padding_in_masked_columns_is_ignored(self):
        """Regression (PR 6): decoders keep padded supports — masked columns
        can hold NaN/inf.  The old `a * mask` produced 0 * NaN = NaN grams;
        the select-based masking must give the same answer as clean padding."""
        rng = np.random.default_rng(4)
        a = rng.normal(size=(20, 6)).astype(np.float32)
        z = (a[:, [0, 2, 3, 5]] @ np.abs(rng.normal(size=4))).astype(np.float32)
        mask = jnp.asarray([True, False, True, True, False, True])
        a_nan = a.copy()
        a_nan[:, 1] = np.nan
        a_nan[:, 4] = np.inf
        beta_clean = nnls_mod.nnls(jnp.asarray(a), jnp.asarray(z), mask)
        beta_nan = nnls_mod.nnls(jnp.asarray(a_nan), jnp.asarray(z), mask)
        assert np.all(np.isfinite(np.asarray(beta_nan)))
        np.testing.assert_allclose(
            np.asarray(beta_nan), np.asarray(beta_clean), atol=1e-6
        )


class TestPRNGStreams:
    def test_streams_pairwise_distinct(self):
        """Regression (PR 6): the signature/frequency/dither streams must come
        from one split fan-out — pairwise-distinct keys for any fixed seed.
        (Previously the dither stream was fold_in(key, 0x51) on the *parent*
        key while sig/freq came from split(key) of the same parent, so the
        derivations were not a single coherent fan-out.)"""
        for seed in (0, 1, 42, 2**31 - 1):
            keys = ckm_mod.stream_keys(jax.random.PRNGKey(seed))
            data = [np.asarray(jax.random.key_data(k)) for k in keys]
            assert len(keys) == 3
            for i in range(3):
                for j in range(i + 1, 3):
                    assert not np.array_equal(data[i], data[j]), (seed, i, j)

    def test_quantizer_and_freqs_use_the_fanout(self):
        """make_quantizer's dither key and _draw_freqs' keys are exactly the
        stream_keys fan-out (no ad-hoc fold_in constants left)."""
        key = jax.random.PRNGKey(7)
        k_sig, k_freq, k_dither = ckm_mod.stream_keys(key)
        cfg = ckm_mod.CKMConfig(k=3, m=16, sketch_quantization="1bit")
        q = ckm_mod.make_quantizer(key, cfg, 16)
        expect = jax.random.uniform(
            k_dither, (16,), minval=0.0, maxval=2.0 * np.pi
        )
        np.testing.assert_array_equal(np.asarray(q.dither), np.asarray(expect))


class TestCompileReuse:
    def test_fit_under_a_new_key_reuses_compiled_programs(self):
        """Regression: the operator's spec (PRNG words, sigma^2) was pytree
        aux data, so every fit under a new key lowered and compiled the
        decoder and the first chunk's update again.  A warm fit under another
        key adds no lowering and no backend compile, and gives bitwise what
        the same key gives from a fresh cache."""
        import jax.monitoring as mon

        cfg = ckm_mod.CKMConfig(
            k=3, m=60, atom_steps=40, joint_steps=30, nnls_iters=40,
            final_steps=80,
        )
        x = synthetic.gaussian_mixture(jax.random.PRNGKey(3), 3000, k=3, n=2,
                                       c=6.0)

        def batches():
            return iter([x[i * 1000:(i + 1) * 1000] for i in range(3)])

        ckm_mod.fit_streaming(jax.random.PRNGKey(1), batches(), cfg)

        events = []

        def listen(event, t0, t1, **kw):
            if event.endswith(("jaxpr_to_mlir_module_duration",
                               "backend_compile_duration")):
                events.append(event)

        mon.register_event_time_span_listener(listen)
        try:
            warm = ckm_mod.fit_streaming(jax.random.PRNGKey(2), batches(), cfg)
            jax.block_until_ready(warm.centroids)
        finally:
            mon.unregister_event_time_span_listener(listen)
        assert events == []
        jax.clear_caches()
        fresh = ckm_mod.fit_streaming(jax.random.PRNGKey(2), batches(), cfg)
        np.testing.assert_array_equal(np.asarray(warm.centroids),
                                      np.asarray(fresh.centroids))
        np.testing.assert_array_equal(np.asarray(warm.cost),
                                      np.asarray(fresh.cost))
