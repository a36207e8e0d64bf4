"""Quantized sketch states (QCKM): monoid laws, parity, end-to-end decode.

The quantized state transform (core/quantize.py + core/engine.py) must keep
the engine's monoid contract *exactly* — integer accumulators make identity,
associativity, commutativity, and split invariance bitwise-testable, no
float tolerance.  Dequantization accuracy is statistical: the 1-bit sketch
matches the float sketch within the dither-noise bound (odd-harmonic leakage
+ O(1/sqrt(N)) code noise; measured rel-l2 ~0.15 on the paper's blobs at
m=200, N=8000), and CLOMPR absorbs that distortion — end-to-end SSE within
10% of the float path is the PR's acceptance criterion, asserted here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import ckm as ckm_mod
from repro.core import engine as eng_mod
from repro.core import freq_ops as fo
from repro.core import frequencies as fq
from repro.core import quantize as qz
from repro.core import sketch as sk
from repro.data import pipeline as pipe
from repro.kernels import ops


def _data(seed, npts=400, n=4, m=24):
    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (npts, n)) * 2.0
    w = fq.draw_frequencies(kw, m, n, 1.0)
    return x, w


def _quantizer(seed, m, spec="1bit"):
    return qz.make_quantizer(jax.random.PRNGKey(1000 + seed), m, spec)


def _int_state_equal(a, b):
    return bool(
        jnp.all(a.qcos_acc == b.qcos_acc) and jnp.all(a.qsin_acc == b.qsin_acc)
    )


class TestParseAndWire:
    def test_parse_bits(self):
        assert qz.parse_bits("none") is None
        assert qz.parse_bits("1bit") == 1
        assert qz.parse_bits("4bit") == 4
        assert qz.parse_bits("16bit") == 16
        for bad in ("2", "0bit", "17bit", "float32", "1 bit no"):
            with pytest.raises(ValueError):
                qz.parse_bits(bad)

    def test_wire_bytes_shrink_with_bits(self):
        float_bytes = qz.state_wire_bytes(1000, 8000, None)
        onebit = qz.state_wire_bytes(1000, 8000, 1)
        eightbit = qz.state_wire_bytes(1000, 8000, 8)
        # 8000 signs fit in int16: 2x smaller than the f32 state; 8-bit code
        # sums over 8000 points genuinely need int32 — same width as float
        # (the model is honest: the win depends on count and depth).
        assert onebit == float_bytes // 2
        assert onebit < eightbit == float_bytes
        # Tiny partials (one batch of 100 points) fit int8: 4x smaller; huge
        # counts fall back to 8-byte lanes instead of crashing.
        assert qz.state_wire_bytes(1000, 100, 1) == float_bytes // 4
        assert qz.state_wire_bytes(1000, 2**40, 16) == float_bytes * 2

    def test_accumulator_capacity_guard(self):
        x, w = _data(9, npts=32)
        e = eng_mod.SketchEngine(w, "xla", quantizer=_quantizer(9, 24, "16bit"))
        s = e.update(e.init_state(), x)
        e.finalize(s)  # under capacity: fine
        over = s._replace(count=jnp.asarray(1e9, jnp.float32))
        with pytest.raises(ValueError, match="overflow"):
            e.finalize(over)
        assert qz.accumulator_capacity(1) == 2**31 - 1

    def test_dither_shape_checked(self):
        _, w = _data(0)
        bad = qz.SketchQuantizer(1, jnp.zeros((7,), jnp.float32))
        with pytest.raises(ValueError):
            eng_mod.SketchEngine(w, "xla", quantizer=bad)


def _f64_signs(theta):
    """float64 ``sign(cos)`` / ``sign(sin)`` of float32 phases, 0 -> +1."""
    t = np.asarray(theta, np.float32).astype(np.float64)
    return np.where(np.cos(t) >= 0, 1, -1), np.where(np.sin(t) >= 0, 1, -1)


def _code_mismatches(theta):
    qc, qs = jax.jit(qz.one_bit_codes)(jnp.asarray(theta, jnp.float32))
    assert qc.dtype == qs.dtype == jnp.int32
    rc, rs = _f64_signs(theta)
    return int(np.sum(np.asarray(qc) != rc) + np.sum(np.asarray(qs) != rs))


def _primitives(jaxpr):
    """Names of every primitive in a jaxpr, nested jaxprs included (jit
    bodies, scans, a ``pallas_call``'s kernel)."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    names |= _primitives(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    names |= _primitives(sub)
    return names


class TestOneBitCodes:
    """The 1-bit code from the phase's quadrant (no cos or sin) equals the
    float64 signs of the same float32 phase."""

    @pytest.mark.parametrize("sign", [1.0, -1.0])
    @pytest.mark.parametrize("amp", [1.0, 10.0, 100.0])
    def test_agrees_with_float64_signs(self, amp, sign):
        rng = np.random.default_rng(int(amp) * 2 + (sign > 0))
        theta = (sign * rng.uniform(0.0, amp, 500_000)).astype(np.float32)
        # At most one disagreement per 10^6 codes (2 codes per phase).
        assert _code_mismatches(theta) <= 1

    @pytest.mark.parametrize("ulps", [-3, -2, -1, 1, 2, 3])
    def test_agrees_a_few_ulps_from_multiples_of_half_pi(self, ulps):
        ks = np.array([k for k in range(-4096, 4097) if k != 0])
        theta = (ks * (np.pi / 2)).astype(np.float32)
        toward = np.float32(np.inf if ulps > 0 else -np.inf)
        for _ in range(abs(ulps)):
            theta = np.nextafter(theta, toward)
        assert _code_mismatches(theta) <= 2 * theta.size // 10**6

    def test_exact_at_zero_and_nearest_multiples_of_half_pi(self):
        """``0`` maps to +1 (as ``sign(sin 0)`` does), and at the float32
        phase nearest ``k pi/2`` each code is the float64 sign there."""
        ks = np.array([k for k in range(-16, 17) if k != 0])
        theta = np.concatenate(
            [np.float32([0.0, -0.0]), (ks * (np.pi / 2)).astype(np.float32)]
        )
        qc, qs = jax.jit(qz.one_bit_codes)(jnp.asarray(theta))
        rc, rs = _f64_signs(theta)
        np.testing.assert_array_equal(np.asarray(qc), rc)
        np.testing.assert_array_equal(np.asarray(qs), rs)
        assert np.asarray(qc)[:2].tolist() == np.asarray(qs)[:2].tolist() == [1, 1]

    @pytest.mark.parametrize("path", ["xla", "pallas_dense", "pallas_structured"])
    @pytest.mark.parametrize("bits", [1, 2])
    def test_only_bbit_codes_compute_trig(self, path, bits):
        """The 1-bit program holds no ``sin`` or ``cos`` on any backend; the
        b-bit code still rounds ``S * cos`` and ``S * sin``."""
        x = jnp.zeros((64, 5), jnp.float32)
        dither = jnp.zeros((40,), jnp.float32)
        if path == "xla":
            fn = lambda x, d: qz.quantize_codes(x @ jnp.ones((5, 40)), d, bits)
        else:
            name = path.split("_")[1]
            op = fo.make_operator(name, jax.random.PRNGKey(0), 40, 5, 1.0)
            fn = lambda x, d: ops.quantized_fourier_sketch_sums(
                x, op, d, bits=bits, block_n=64, block_m=128, interpret=True
            )
        prims = _primitives(jax.make_jaxpr(fn)(x, dither).jaxpr)
        has_trig = bool({"sin", "cos"} & prims)
        assert has_trig == (bits > 1), sorted(prims)


class TestQuantizedMonoidLaws:
    """The laws hold *bitwise* — integer sums have no rounding."""

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        cut_a=st.integers(1, 197),
        cut_b=st.integers(199, 398),
        spec=st.sampled_from(["1bit", "4bit"]),
    )
    def test_merge_associative_and_commutative(self, seed, cut_a, cut_b, spec):
        x, w = _data(seed)
        e = eng_mod.SketchEngine(w, "xla", chunk=64, quantizer=_quantizer(seed, 24, spec))
        parts = [x[:cut_a], x[cut_a:cut_b], x[cut_b:]]
        a, b, c = (e.update(e.init_state(), p) for p in parts)
        left = e.merge(e.merge(a, b), c)
        right = e.merge(a, e.merge(b, c))
        assert _int_state_equal(left, right)
        assert _int_state_equal(e.merge(a, b), e.merge(b, a))
        np.testing.assert_allclose(np.asarray(left.lower), np.asarray(right.lower))
        np.testing.assert_allclose(np.asarray(left.upper), np.asarray(right.upper))

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n_chunks=st.integers(1, 9))
    def test_split_invariance_is_exact(self, seed, n_chunks):
        """Codes are deterministic per point, so ANY batching of the same
        points yields the bitwise-identical integer state."""
        x, w = _data(seed)
        e = eng_mod.SketchEngine(w, "xla", chunk=128, quantizer=_quantizer(seed, 24))
        one_shot = e.update(e.init_state(), x)
        state = e.init_state()
        for batch in pipe.chunked(x, max(1, x.shape[0] // n_chunks)):
            state = e.update(state, batch)
        assert _int_state_equal(one_shot, state)
        assert float(one_shot.count) == float(state.count) == x.shape[0]

    def test_identity_element(self):
        x, w = _data(3)
        e = eng_mod.SketchEngine(w, "xla", quantizer=_quantizer(3, 24))
        s = e.update(e.init_state(), x)
        for combined in (e.merge(s, e.init_state()), e.merge(e.init_state(), s)):
            assert _int_state_equal(combined, s)
            for za, zb in zip(e.finalize(combined), e.finalize(s)):
                np.testing.assert_allclose(np.asarray(za), np.asarray(zb))

    def test_state_is_integer_and_weights_rejected(self):
        x, w = _data(5)
        e = eng_mod.SketchEngine(w, "xla", quantizer=_quantizer(5, 24))
        s = e.update(e.init_state(), x)
        assert s.qcos_acc.dtype == jnp.int32 and s.qsin_acc.dtype == jnp.int32
        # 1-bit codes: each accumulator entry is bounded by the point count.
        assert int(jnp.max(jnp.abs(s.qcos_acc))) <= x.shape[0]
        with pytest.raises(ValueError):
            e.update(e.init_state(), x, jnp.ones((x.shape[0],)))


class TestDequantization:
    def test_1bit_matches_float_within_dither_noise_bound(self, gaussian_blobs):
        """(pi/4) E[sign] correction on the paper's blobs: rel-l2 within the
        odd-harmonic + code-noise bound (~0.15 measured; 0.25 asserted)."""
        x, _, _ = gaussian_blobs
        k_sig, k_w = jax.random.split(jax.random.PRNGKey(1))
        sigma2 = fq.estimate_sigma2(k_sig, x[:2048])
        w = fq.draw_frequencies(k_w, 200, x.shape[1], sigma2)
        z_ref = np.asarray(sk.sketch(x, w))
        e = eng_mod.SketchEngine(w, "xla", quantizer=_quantizer(0, 200))
        z, lo, hi = e.sketch(x)
        rel = np.linalg.norm(np.asarray(z) - z_ref) / np.linalg.norm(z_ref)
        assert rel < 0.25, rel
        np.testing.assert_allclose(np.asarray(lo), np.asarray(x.min(0)), atol=1e-6)
        np.testing.assert_allclose(np.asarray(hi), np.asarray(x.max(0)), atol=1e-6)

    def test_bbit_error_shrinks_with_depth(self, gaussian_blobs):
        """b-bit rounding error ~ 1/S: 8-bit is near-float, 4bit in between."""
        x, _, _ = gaussian_blobs
        k_sig, k_w = jax.random.split(jax.random.PRNGKey(2))
        sigma2 = fq.estimate_sigma2(k_sig, x[:2048])
        w = fq.draw_frequencies(k_w, 200, x.shape[1], sigma2)
        z_ref = np.asarray(sk.sketch(x, w))
        errs = {}
        for spec in ("4bit", "8bit"):
            e = eng_mod.SketchEngine(w, "xla", quantizer=_quantizer(0, 200, spec))
            z, _, _ = e.sketch(x)
            errs[spec] = float(np.max(np.abs(np.asarray(z) - z_ref)))
        assert errs["8bit"] < 2e-3, errs
        assert errs["4bit"] < 2e-2, errs
        assert errs["8bit"] < errs["4bit"]


class TestQuantizedBackendParity:
    def test_pallas_matches_xla_bitwise(self):
        """Fused int32 kernel == XLA scan, exact — ragged N, unaligned m."""
        x, w = _data(0, npts=777, n=6, m=100)
        for spec in ("1bit", "6bit"):
            q = _quantizer(0, 100, spec)
            e_x = eng_mod.SketchEngine(w, "xla", quantizer=q)
            e_p = eng_mod.SketchEngine(
                w, "pallas", block_n=256, block_m=128, quantizer=q
            )
            s_x = e_x.update(e_x.init_state(), x)
            s_p = e_p.update(e_p.init_state(), x)
            assert _int_state_equal(s_x, s_p), spec
            for za, zb in zip(e_x.finalize(s_x), e_p.finalize(s_p)):
                np.testing.assert_allclose(np.asarray(za), np.asarray(zb))

    @pytest.mark.parametrize("bits", [1, 2, 3])
    @pytest.mark.parametrize("family", ["dense", "structured"])
    def test_pallas_matches_xla_bitwise_per_depth(self, family, bits):
        """Both fused kernels make ``quantize_codes``' codes at every depth.
        2 bits is the depth whose scale (1) equals the 1-bit code's: its
        codes are round(cos), not signs."""
        x, _ = _data(3, npts=333, n=5)
        op = fo.make_operator(family, jax.random.PRNGKey(4), 60, 5, 1.0)
        q = _quantizer(3, 60, f"{bits}bit")
        e_x = eng_mod.SketchEngine(op, "xla", quantizer=q)
        e_p = eng_mod.SketchEngine(
            op, "pallas", block_n=128, block_m=128, quantizer=q
        )
        s_x = e_x.update(e_x.init_state(), x)
        s_p = e_p.update(e_p.init_state(), x)
        assert _int_state_equal(s_x, s_p)

    def test_sharded_psums_integer_accumulators(self):
        """Acceptance: the sharded backend merges int accumulators (psum over
        the mesh) bitwise-equal to the xla path, ragged streams included."""
        import os
        import subprocess
        import sys
        import textwrap

        prog = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, numpy as np, jax.numpy as jnp
            from repro.core import engine as eng_mod
            from repro.core import frequencies as fq
            from repro.core import quantize as qz
            from repro.data.pipeline import chunked

            key = jax.random.PRNGKey(0)
            kx, kw, kd = jax.random.split(key, 3)
            x = jax.random.normal(kx, (4096, 6))
            w = fq.draw_frequencies(kw, 48, 6, 1.0)
            q = qz.SketchQuantizer(1, qz.draw_dither(kd, 48))
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            e_x = eng_mod.SketchEngine(w, "xla", chunk=512, quantizer=q)
            e_s = eng_mod.SketchEngine(w, "sharded", mesh=mesh, chunk=512,
                                       quantizer=q)
            s_x = e_x.update(e_x.init_state(), x)
            s_s = e_s.update(e_s.init_state(), x)
            assert s_s.qcos_acc.dtype == jnp.int32
            assert bool(jnp.all(s_x.qcos_acc == s_s.qcos_acc))
            assert bool(jnp.all(s_x.qsin_acc == s_s.qsin_acc))
            assert float(s_s.count) == 4096.0
            # Ragged stream: zero-valid padding must not move the int sums.
            z_s, lo, hi = e_s.sketch_stream(chunked(x[:4003], 1000))
            z_x, lo_x, hi_x = e_x.sketch_stream(chunked(x[:4003], 1000))
            np.testing.assert_allclose(np.asarray(z_s), np.asarray(z_x),
                                       atol=1e-6)
            np.testing.assert_allclose(np.asarray(lo), np.asarray(lo_x))
            np.testing.assert_allclose(np.asarray(hi), np.asarray(hi_x))
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


@pytest.mark.slow
class TestQuantizedCKM:
    def test_fit_streaming_1bit_recovers_blobs(self, gaussian_blobs):
        """Acceptance: one-pass 1-bit quantized fit localises every true mean
        (Hungarian-matched error < 1.0), like the float streaming fit."""
        x, _, means = gaussian_blobs
        cfg = ckm_mod.CKMConfig(k=5, sketch_quantization="1bit")
        res = ckm_mod.fit_streaming(
            jax.random.PRNGKey(0), pipe.chunked(x, 1000), cfg
        )
        assert res.sketch.shape == (2 * cfg.sketch_size(x.shape[1]),)
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

    def test_1bit_sse_within_10pct_of_float(self, gaussian_blobs):
        """Acceptance: quantized-vs-float centroid SSE within 10% relative."""
        x, _, _ = gaussian_blobs
        key = jax.random.PRNGKey(0)
        sse = {}
        for quant in ("none", "1bit"):
            cfg = ckm_mod.CKMConfig(k=5, sketch_quantization=quant)
            res = ckm_mod.fit(key, x, cfg)
            sse[quant] = float(ckm_mod.sse(x, res.centroids))
        assert sse["1bit"] <= 1.10 * sse["none"], sse

    def test_1bit_fit_on_pallas_backend(self, gaussian_blobs):
        """Acceptance: sketch_quantization='1bit' end-to-end on the pallas
        backend (fused int32 encoder; sharded is covered bitwise above)."""
        x, _, means = gaussian_blobs
        cfg = ckm_mod.CKMConfig(
            k=5, sketch_quantization="1bit", sketch_backend="pallas"
        )
        res = ckm_mod.fit(jax.random.PRNGKey(0), x, cfg)
        d = np.linalg.norm(
            np.asarray(means)[:, None] - np.asarray(res.centroids)[None], axis=-1
        )
        assert float(np.max(np.min(d, axis=1))) < 1.0
