"""SketchEngine: monoid laws, backend parity, streaming end-to-end.

The engine's contract (core/engine.py) is that the sketch state is a
commutative monoid and every backend computes the same sketch.  The property
tests draw arbitrary batch splits / merge orders; the parity tests pin the
three backends (pallas in interpret mode on CPU) to the reference
``core.sketch.sketch`` within 1e-4.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import ckm as ckm_mod
from repro.core import engine as eng_mod
from repro.core import frequencies as fq
from repro.core import sketch as sk
from repro.data import pipeline as pipe


def _data(seed, npts=400, n=4, m=24):
    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (npts, n)) * 2.0
    w = fq.draw_frequencies(kw, m, n, 1.0)
    return x, w


class TestMonoidLaws:
    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        cut_a=st.integers(1, 197),
        cut_b=st.integers(199, 398),
    )
    def test_merge_associative_and_commutative(self, seed, cut_a, cut_b):
        """(a+b)+c == a+(b+c) and a+b == b+a for arbitrary 3-way splits."""
        x, w = _data(seed)
        e = eng_mod.SketchEngine(w, "xla", chunk=64)
        parts = [x[:cut_a], x[cut_a:cut_b], x[cut_b:]]
        a, b, c = (e.update(e.init_state(), p) for p in parts)
        left = e.merge(e.merge(a, b), c)
        right = e.merge(a, e.merge(b, c))
        for zl, zr in zip(e.finalize(left), e.finalize(right)):
            np.testing.assert_allclose(np.asarray(zl), np.asarray(zr), atol=1e-5)
        ab, ba = e.merge(a, b), e.merge(b, a)
        for zl, zr in zip(e.finalize(ab), e.finalize(ba)):
            np.testing.assert_allclose(np.asarray(zl), np.asarray(zr), atol=1e-6)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n_chunks=st.integers(1, 9))
    def test_update_splits_equal_one_shot_sketch(self, seed, n_chunks):
        """update-then-finalize over any batch split == core.sketch.sketch."""
        x, w = _data(seed)
        e = eng_mod.SketchEngine(w, "xla", chunk=128)
        size = max(1, x.shape[0] // n_chunks)
        state = e.init_state()
        for batch in pipe.chunked(x, size):
            state = e.update(state, batch)
        z, lo, hi = e.finalize(state)
        np.testing.assert_allclose(
            np.asarray(z), np.asarray(sk.sketch(x, w)), atol=1e-4
        )
        np.testing.assert_allclose(np.asarray(lo), np.asarray(x.min(0)), atol=1e-6)
        np.testing.assert_allclose(np.asarray(hi), np.asarray(x.max(0)), atol=1e-6)

    def test_identity_element(self, rng):
        x, w = _data(3)
        e = eng_mod.SketchEngine(w, "xla")
        s = e.update(e.init_state(), x)
        for combined in (e.merge(s, e.init_state()), e.merge(e.init_state(), s)):
            for za, zb in zip(e.finalize(combined), e.finalize(s)):
                np.testing.assert_allclose(np.asarray(za), np.asarray(zb))

    def test_weighted_updates(self, rng):
        """Engine with explicit weights == weighted core sketch."""
        x, w = _data(7, npts=200)
        kb = jax.random.PRNGKey(11)
        beta = jax.random.uniform(kb, (200,), minval=0.1)
        e = eng_mod.SketchEngine(w, "xla")
        s = e.update(e.init_state(), x[:90], beta[:90])
        s = e.update(s, x[90:], beta[90:])
        z, *_ = e.finalize(s)
        ref = sk.sketch(x, w, weights=beta / jnp.sum(beta))
        np.testing.assert_allclose(np.asarray(z), np.asarray(ref), atol=1e-4)


class TestZeroWeightFinalize:
    """Regression (PR 6): finalize divides by ``weight_sum``; an empty stream
    (or an all-zero-weight shard) must produce the *zero sketch* — explicitly
    guarded, not left to ``0 / denom-floor`` luck — for the float and the
    quantized state flavours alike."""

    def _engines(self, quantized):
        from repro.core import quantize as qz

        _, w = _data(5, npts=8, m=24)
        q = (
            qz.make_quantizer(jax.random.PRNGKey(3), 24, "1bit")
            if quantized
            else None
        )
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1), ("data", "model"))
        return {
            "xla": eng_mod.SketchEngine(w, "xla", quantizer=q),
            "pallas": eng_mod.SketchEngine(
                w, "pallas", block_n=128, block_m=128, quantizer=q
            ),
            "sharded": eng_mod.SketchEngine(
                w, "sharded", mesh=mesh, quantizer=q
            ),
        }

    @pytest.mark.parametrize("quantized", [False, True], ids=["float", "1bit"])
    def test_empty_stream_finalizes_to_zero_sketch(self, quantized):
        for name, e in self._engines(quantized).items():
            z, _, _ = e.finalize(e.init_state())
            np.testing.assert_array_equal(
                np.asarray(z), np.zeros(48, np.float32), err_msg=name
            )

    def test_zero_weight_updates_finalize_to_zero_sketch(self):
        # Float states only: the quantized flavour rejects per-point weights
        # (integer counts), so its zero-weight case is the empty stream above.
        x, _ = _data(5, npts=64, m=24)
        for name, e in self._engines(False).items():
            if name == "sharded":
                continue  # shard_points needs >= data-axis rows; covered above
            s = e.update(e.init_state(), x, jnp.zeros((64,)))
            z, _, _ = e.finalize(s)
            np.testing.assert_array_equal(
                np.asarray(z), np.zeros(48, np.float32), err_msg=name
            )
            assert float(getattr(s, "weight_sum")) == 0.0


class TestBackendParity:
    def test_pallas_matches_xla_within_1e4(self):
        """Acceptance: pallas (interpret on CPU) == xla backend within 1e-4."""
        x, w = _data(0, npts=777, n=6, m=100)  # ragged N, unaligned m
        z_x, lo_x, hi_x = eng_mod.SketchEngine(w, "xla").sketch(x)
        z_p, lo_p, hi_p = eng_mod.SketchEngine(
            w, "pallas", block_n=256, block_m=128
        ).sketch(x)
        np.testing.assert_allclose(np.asarray(z_p), np.asarray(z_x), atol=1e-4)
        np.testing.assert_allclose(np.asarray(lo_p), np.asarray(lo_x), atol=1e-6)
        np.testing.assert_allclose(np.asarray(hi_p), np.asarray(hi_x), atol=1e-6)

    def test_all_backends_match_reference_sketch(self):
        """Acceptance: every backend == core.sketch.sketch within 1e-4
        (sharded runs in a subprocess with a forced 8-device host platform)."""
        import os
        import subprocess
        import sys
        import textwrap

        prog = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, numpy as np
            from repro.core import engine as eng_mod
            from repro.core import frequencies as fq
            from repro.core import sketch as sk

            key = jax.random.PRNGKey(0)
            kx, kw = jax.random.split(key)
            x = jax.random.normal(kx, (4096, 6))
            w = fq.draw_frequencies(kw, 48, 6, 1.0)
            z_ref = np.asarray(sk.sketch(x, w))

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            engines = {
                "xla": eng_mod.SketchEngine(w, "xla", chunk=512),
                "pallas": eng_mod.SketchEngine(w, "pallas", block_n=512,
                                               block_m=128),
                "sharded": eng_mod.SketchEngine(w, "sharded", mesh=mesh,
                                                chunk=512),
            }
            for name, e in engines.items():
                xin = e.shard_points(x) if name == "sharded" else x
                z, lo, hi = e.sketch(xin)
                err = float(np.max(np.abs(np.asarray(z) - z_ref)))
                assert err < 1e-4, (name, err)
                np.testing.assert_allclose(np.asarray(lo), np.asarray(x.min(0)),
                                           atol=1e-6)
            # Ragged streaming through the sharded backend: tail chunks not
            # divisible by the data-axis extent are zero-weight padded.
            from repro.data.pipeline import chunked
            e = engines["sharded"]
            z, lo, hi = e.sketch_stream(chunked(x[:4003], 1000))
            err = float(np.max(np.abs(
                np.asarray(z) - np.asarray(sk.sketch(x[:4003], w)))))
            assert err < 1e-4, ("sharded-ragged", err)
            np.testing.assert_allclose(np.asarray(lo),
                                       np.asarray(x[:4003].min(0)), atol=1e-6)
            print("OK")
            """
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True,
            text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-3000:]
        assert "OK" in out.stdout

    def test_bad_backend_rejected(self):
        _, w = _data(0, npts=8)
        with pytest.raises(ValueError):
            eng_mod.SketchEngine(w, "tpu9000")
        with pytest.raises(ValueError):
            eng_mod.SketchEngine(w, "sharded")  # no mesh


@pytest.mark.slow
class TestStreamingCKM:
    def test_fit_streaming_recovers_blobs(self, gaussian_blobs):
        """Acceptance: one-pass fit over a chunked iterator localises every
        true mean (Hungarian-matched error < 1.0), like in-memory fit."""
        x, _, means = gaussian_blobs
        cfg = ckm_mod.CKMConfig(k=5)
        res = ckm_mod.fit_streaming(
            jax.random.PRNGKey(0), pipe.chunked(x, 1000), cfg
        )
        d = np.linalg.norm(
            np.asarray(means)[:, None] - np.asarray(res.centroids)[None], axis=-1
        ).copy()
        errs = []
        for _ in range(means.shape[0]):
            i, j = np.unravel_index(np.argmin(d), d.shape)
            errs.append(d[i, j])
            d[i, :] = np.inf
            d[:, j] = np.inf
        assert np.all(np.array(errs) < 1.0), errs

    def test_streaming_sketch_equals_in_memory_sketch(self, gaussian_blobs):
        """Same key -> streaming and in-memory fits see the same (z, w, l, u)."""
        x, _, _ = gaussian_blobs
        cfg = ckm_mod.CKMConfig(k=5, sigma2=1.0, sigma2_sample=1000)
        key = jax.random.PRNGKey(9)
        z_mem, op_mem, _, (lo_m, hi_m) = ckm_mod.compute_sketch(key, x, cfg)
        z_st, op_st, _, (lo_s, hi_s), _ = ckm_mod.compute_sketch_streaming(
            key, pipe.chunked(x, 1000), cfg
        )
        # Same key -> the same operator spec (and hence identical frequencies).
        assert op_st.spec() == op_mem.spec()
        np.testing.assert_allclose(
            np.asarray(op_st.materialize()), np.asarray(op_mem.materialize())
        )
        np.testing.assert_allclose(np.asarray(z_st), np.asarray(z_mem), atol=1e-4)
        np.testing.assert_allclose(np.asarray(lo_s), np.asarray(lo_m), atol=1e-6)
        np.testing.assert_allclose(np.asarray(hi_s), np.asarray(hi_m), atol=1e-6)

    def test_embedding_stream_feeds_engine(self):
        """The data pipeline's embedding stream plugs into the engine."""
        from repro.configs.base import ShapeConfig, get_smoke_config
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_smoke_config("llama3.2-1b")
        shape = ShapeConfig("t", 16, 8, "train")
        src = SyntheticLM(cfg, shape, DataConfig(seed=0, embed_dim=8))
        w = fq.draw_frequencies(jax.random.PRNGKey(0), 16, 8, 1.0)
        e = eng_mod.SketchEngine(w, "xla")
        z, lo, hi = e.sketch_stream(src.embedding_stream(0, 4))
        assert z.shape == (32,) and np.all(np.isfinite(np.asarray(z)))
        assert bool(jnp.all(lo <= hi))
