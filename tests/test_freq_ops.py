"""Frequency-operator subsystem tests (marker: freq_ops).

The contract (``core/freq_ops``): operators are registered by name, expose
``apply``/``adjoint``/``materialize``/``col_norms``/``spec``, and thread
end-to-end (sketch -> engine backends -> quantization -> decoders).  The
acceptance pins:

- ``freq_op="dense"`` through the registry is **bitwise identical** to the
  pre-refactor dense-matrix path on all three backends (the xla replica here
  is a verbatim copy of the pre-refactor chunked-scan math);
- the structured fast transform agrees with its dense materialisation, its
  adjoint is the true transpose, and its column norms follow the drawn
  adapted radii exactly (the radial-rescaling property);
- ``spec()`` rebuilds operators exactly and is O(1) bytes;
- the raw ``(n, m)`` convenience wrap still works on the sketch/engine entry
  points, while the decoder helpers and kernel wrappers raise ``TypeError``
  (their deprecation window closed in PR 6);
- ``draw_frequencies`` takes a ``dtype`` and the radius inverse-CDF sampler
  agrees between f32 and f64 on identical uniforms;
- ``estimate_sigma2`` recovers the within-cluster scale within 2x on
  synthetic Gaussian blobs across seeds.
"""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ckm as ckm_mod
from repro.core import engine as eng_mod
from repro.core import freq_ops as fo
from repro.core import frequencies as fq
from repro.core import quantize as qz
from repro.core import sketch as sk
from repro.core.decoders import common as dec_common
from repro.kernels import ref

pytestmark = pytest.mark.freq_ops


@functools.partial(jax.jit, static_argnames=("chunk",))
def _pre_refactor_sketch(x, w, chunk=8192):
    """Verbatim copy of the pre-refactor ``core.sketch.sketch`` math
    (uniform weights): the bitwise oracle for the dense registry path."""
    x = jnp.asarray(x, jnp.float32)
    n_pts = x.shape[0]
    m = w.shape[1]
    weights = jnp.full((n_pts,), 1.0 / n_pts, jnp.float32)
    pad = (-n_pts) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
        weights = jnp.concatenate([weights, jnp.zeros((pad,), weights.dtype)], axis=0)
    n_chunks = x.shape[0] // chunk
    xs = x.reshape(n_chunks, chunk, -1)
    ws_ = weights.reshape(n_chunks, chunk)

    def body(acc, inp):
        xc, bc = inp
        proj = xc @ w
        return (acc[0] + bc @ jnp.cos(proj), acc[1] + bc @ jnp.sin(proj)), None

    acc0 = jnp.zeros((m,), jnp.float32)
    (cos_acc, sin_acc), _ = jax.lax.scan(body, (acc0, acc0), (xs, ws_))
    return jnp.concatenate([cos_acc, -sin_acc])


def _ops(n=6, m=80, sigma2=1.3, seed=5):
    key = jax.random.PRNGKey(seed)
    return {
        name: fo.make_operator(name, key, m, n, sigma2)
        for name in fo.available_freq_ops()
    }


class TestRegistry:
    def test_builtins_registered(self):
        assert set(fo.available_freq_ops()) >= {"dense", "structured"}

    def test_unknown_name_raises_with_names(self):
        with pytest.raises(KeyError, match="dense"):
            fo.get_freq_op("fourier9000")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            fo.register_freq_op("dense")(lambda *a, **k: None)

    def test_custom_operator_threads_through_config(self):
        """A user-registered family is selectable via CKMConfig.freq_op."""
        name = "test_scaled_dense"
        fo.FREQ_OPS.pop(name, None)

        @fo.register_freq_op(name)
        def build(key, m, n, sigma2, *, dist="adapted_radius", dtype=jnp.float32):
            base = fo.make_operator("dense", key, m, n, sigma2, dist=dist,
                                    dtype=dtype)
            return fo.DenseOperator(0.5 * base.w)

        try:
            x = jax.random.normal(jax.random.PRNGKey(0), (256, 3))
            cfg = ckm_mod.CKMConfig(
                k=2, m=24, sigma2=1.0, freq_op=name,
                atom_steps=5, joint_steps=5, nnls_iters=5, final_steps=5,
            )
            res = ckm_mod.fit(jax.random.PRNGKey(1), x, cfg)
            assert res.centroids.shape == (2, 3)
            assert isinstance(res.freq_op, fo.DenseOperator)
        finally:
            fo.FREQ_OPS.pop(name)


class TestDenseBitwiseIdentity:
    """Acceptance: the registry dense path == the pre-refactor dense path,
    bit for bit, on every backend."""

    def test_xla_sketch_bitwise(self):
        key = jax.random.PRNGKey(3)
        kx, kf = jax.random.split(key)
        x = jax.random.normal(kx, (1003, 6)) * 2.0
        sigma2 = jnp.asarray(1.7, jnp.float32)
        w = fq.draw_frequencies(kf, 48, 6, sigma2)
        op = fo.make_operator("dense", kf, 48, 6, sigma2)
        # Same key -> the drawn matrix itself is bitwise identical...
        assert bool(jnp.array_equal(op.w, w))
        # ...and the chunked-scan sketch through the operator matches the
        # pre-refactor math exactly (same jaxpr: op.apply IS `x @ w`).
        z_op = sk.sketch(x, op, chunk=256)
        z_old = _pre_refactor_sketch(x, w, chunk=256)
        assert bool(jnp.array_equal(z_op, z_old))

    def test_engine_backends_bitwise_raw_vs_operator(self):
        """Raw-matrix engines (shim) and operator engines agree bitwise on
        xla and pallas; the sharded backend is covered in a subprocess."""
        key = jax.random.PRNGKey(4)
        kx, kf = jax.random.split(key)
        x = jax.random.normal(kx, (777, 5))
        op = fo.make_operator("dense", kf, 40, 5, 1.0)
        for backend, kw in (("xla", {}), ("pallas", dict(block_n=256, block_m=128))):
            z_raw, lo_r, hi_r = eng_mod.SketchEngine(op.w, backend, **kw).sketch(x)
            z_op, lo_o, hi_o = eng_mod.SketchEngine(op, backend, **kw).sketch(x)
            assert bool(jnp.array_equal(z_raw, z_op)), backend
            assert bool(jnp.array_equal(lo_r, lo_o) and jnp.array_equal(hi_r, hi_o))

    def test_sharded_backend_bitwise(self):
        """Sharded backend: operator-carried engine == raw-matrix engine,
        bitwise, in a forced-8-device subprocess."""
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
            from repro.core import freq_ops as fo

            key = jax.random.PRNGKey(0)
            kx, kf = jax.random.split(key)
            x = jax.random.normal(kx, (4096, 6))
            op = fo.make_operator("dense", kf, 48, 6, 1.0)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            z_raw, lo_r, hi_r = eng_mod.SketchEngine(
                op.w, "sharded", mesh=mesh, chunk=512).sketch(x)
            z_op, lo_o, hi_o = eng_mod.SketchEngine(
                op, "sharded", mesh=mesh, chunk=512).sketch(x)
            assert bool(jnp.array_equal(z_raw, z_op))
            assert bool(jnp.array_equal(lo_r, lo_o))
            # The structured family runs through the same sharded machinery
            # (the operator pytree rides shard_map replicated).
            s_op = fo.make_operator("structured", kf, 48, 6, 1.0)
            z_sh, _, _ = eng_mod.SketchEngine(
                s_op, "sharded", mesh=mesh, chunk=512).sketch(x)
            z_x, _, _ = eng_mod.SketchEngine(s_op, "xla", chunk=512).sketch(x)
            err = float(np.max(np.abs(np.asarray(z_sh) - np.asarray(z_x))))
            assert err < 1e-4, err
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


class TestStructuredAlgebra:
    @pytest.mark.parametrize("n,m", [(6, 80), (16, 16), (5, 7), (33, 100)])
    def test_apply_matches_materialize(self, n, m):
        op = _ops(n=n, m=m)["structured"]
        x = jax.random.normal(jax.random.PRNGKey(1), (17, n))
        W = op.materialize()
        assert W.shape == (n, m)
        np.testing.assert_allclose(
            np.asarray(op.apply(x)), np.asarray(x @ W), atol=1e-4
        )

    def test_apply_matches_explicit_hadamard_oracle(self):
        """Independent oracle: explicit Sylvester-Hadamard matmuls (ref.py)."""
        op = _ops(n=24, m=100)["structured"]
        x = jax.random.normal(jax.random.PRNGKey(2), (31, 24))
        want = ref.structured_project_ref(x, op.diags, op.radii)[:, : op.m]
        np.testing.assert_allclose(
            np.asarray(op.apply(x)), np.asarray(want), rtol=1e-4, atol=1e-4
        )

    def test_adjoint_is_transpose(self):
        op = _ops()["structured"]
        x = jax.random.normal(jax.random.PRNGKey(3), (9, op.n))
        v = jax.random.normal(jax.random.PRNGKey(4), (9, op.m))
        W = np.asarray(op.materialize())
        np.testing.assert_allclose(
            np.asarray(op.adjoint(v)), np.asarray(v) @ W.T, atol=1e-4
        )
        # <apply(x), v> == <x, adjoint(v)> — the defining identity.
        lhs = float(jnp.sum(op.apply(x) * v))
        rhs = float(jnp.sum(x * op.adjoint(v)))
        assert abs(lhs - rhs) < 1e-2 * max(1.0, abs(lhs))

    def test_radial_rescaling_exact(self):
        """||omega_j|| equals the drawn adapted radius exactly — the
        "adapted-radius radial rescaling" of the tentpole."""
        op = _ops(n=10, m=64)["structured"]
        W = np.asarray(op.materialize())
        np.testing.assert_allclose(
            np.linalg.norm(W, axis=0), np.asarray(op.col_norms()), rtol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(op.col_norms()), np.asarray(op.rho.reshape(-1)[: op.m])
        )

    def test_atom_norm_preserved(self):
        """|A delta_c| has unit modulus per frequency for ANY operator —
        CLOMPR's sqrt(m) normalisation stays valid."""
        for name, op in _ops(n=7, m=33).items():
            cs = jax.random.normal(jax.random.PRNGKey(5), (4, 7)) * 3.0
            a = sk.atoms(cs, op)
            np.testing.assert_allclose(
                np.linalg.norm(np.asarray(a), axis=1),
                np.full(4, np.sqrt(33.0)),
                rtol=1e-5,
                err_msg=name,
            )

    def test_grad_flows_through_apply(self):
        """Decoders autodiff through the fast transform."""
        op = _ops()["structured"]

        def f(c):
            return jnp.sum(jnp.cos(op.apply(c)))

        g = jax.grad(f)(jnp.ones((op.n,)))
        assert g.shape == (op.n,) and bool(jnp.all(jnp.isfinite(g)))


class TestSpec:
    @pytest.mark.parametrize("name", ["dense", "structured"])
    def test_roundtrip_exact(self, name):
        op = _ops()[name]
        spec = op.spec()
        op2 = fo.from_spec(spec)
        for a, b in zip(jax.tree.leaves(op), jax.tree.leaves(op2)):
            assert bool(jnp.array_equal(a, b))
        assert op2.spec() == spec

    @pytest.mark.parametrize("name", ["dense", "structured"])
    def test_spec_is_o1_bytes(self, name):
        op = _ops(n=64, m=512)[name]
        spec_bytes = fo.spec_wire_bytes(op.spec())
        matrix_bytes = 4 * 64 * 512
        assert spec_bytes < 128
        assert spec_bytes < 0.01 * matrix_bytes

    def test_structured_state_is_o_m(self):
        """The operator's leaves are O(m) — what a by-value carry would ship
        — vs the O(n·m) dense matrix."""
        n, m = 256, 2048
        ops = _ops(n=n, m=m)
        assert ops["structured"].state_bytes() < 0.1 * ops["dense"].state_bytes()

    @pytest.mark.parametrize("name", ["dense", "structured"])
    def test_treedef_ignores_key_and_sigma2(self, name):
        """The spec is host bookkeeping, not pytree aux data: operators that
        differ in key and sigma^2 share one treedef (so one jit cache entry),
        the eager operators keep their own recipes, and an operator rebuilt
        from its leaves carries none."""
        a = fo.make_operator(name, jax.random.PRNGKey(1), 80, 6, 0.7)
        b = fo.make_operator(name, jax.random.PRNGKey(2), 80, 6, 1.9)
        assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
        for op, seed, sigma2 in ((a, 1, 0.7), (b, 2, 1.9)):
            spec = op.spec()
            assert spec.key_data == fo.base.key_data_tuple(
                jax.random.PRNGKey(seed))
            assert spec.sigma2 == pytest.approx(sigma2)
            rebuilt = fo.from_spec(spec)
            for x, y in zip(jax.tree.leaves(op), jax.tree.leaves(rebuilt)):
                assert bool(jnp.array_equal(x, y))
        out = jax.jit(lambda o: o)(a)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(out)):
            assert bool(jnp.array_equal(x, y))
        with pytest.raises(ValueError, match="no spec"):
            out.spec()

    def test_raw_matrix_has_no_spec(self):
        w = jnp.ones((3, 8))
        with pytest.raises(ValueError, match="no spec"):
            fo.as_operator(w).spec()

    def test_engine_exposes_spec(self):
        op = _ops()["structured"]
        eng = eng_mod.SketchEngine(op, "xla")
        assert eng.spec() == op.spec()
        assert eng.w.shape == (op.n, op.m)  # back-compat materialisation


class TestDeprecationShim:
    def test_decoder_helpers_reject_raw_matrix(self):
        """Satellite (PR 6): the one-release raw-array window is closed —
        the decoder helpers now raise TypeError instead of warning."""
        op = _ops()["dense"]
        z = jnp.ones((2 * op.m,))
        cents = jnp.zeros((3, op.n))
        alpha = jnp.ones((3,)) / 3.0
        with pytest.raises(TypeError, match="as_operator"):
            dec_common.residual_cost(z, cents, alpha, op.w)
        with pytest.raises(TypeError, match="as_operator"):
            dec_common.resolution_radius(op.w, 2.5)
        # The explicit wrap is the supported path and matches the operator.
        raw = dec_common.residual_cost(z, cents, alpha, fo.as_operator(op.w))
        via_op = dec_common.residual_cost(z, cents, alpha, op)
        assert bool(jnp.array_equal(raw, via_op))

    def test_kernel_wrappers_reject_raw_matrix(self):
        """kernels.ops closed the same window: raw w -> TypeError."""
        from repro.kernels import ops

        op = _ops()["dense"]
        x = jax.random.normal(jax.random.PRNGKey(1), (32, op.n))
        with pytest.raises(TypeError, match="as_operator"):
            ops.fourier_sketch(x, op.w, jnp.full((32,), 1.0 / 32))

    def test_sketch_and_engine_accept_raw_silently(self):
        """The convenience wrap: raw w keeps working here without noise."""
        op = _ops()["dense"]
        x = jax.random.normal(jax.random.PRNGKey(0), (64, op.n))
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            z = sk.sketch(x, op.w)
            eng_mod.SketchEngine(op.w, "xla").sketch(x)
        assert z.shape == (2 * op.m,)


class TestBackendParityStructured:
    def test_pallas_matches_xla(self):
        op = _ops(n=11, m=70)["structured"]
        x = jax.random.normal(jax.random.PRNGKey(6), (513, 11))
        z_x, lo_x, hi_x = eng_mod.SketchEngine(op, "xla").sketch(x)
        z_p, lo_p, hi_p = eng_mod.SketchEngine(op, "pallas", block_n=128).sketch(x)
        np.testing.assert_allclose(np.asarray(z_p), np.asarray(z_x), atol=1e-4)
        np.testing.assert_allclose(np.asarray(lo_p), np.asarray(lo_x), atol=1e-6)

    def test_quantized_pallas_bitwise_matches_xla(self):
        """Integer code sums are exact: the fused structured QCKM kernel must
        agree with the XLA chunked path bit for bit."""
        op = _ops(n=9, m=50)["structured"]
        x = jax.random.normal(jax.random.PRNGKey(7), (300, 9))
        for bits in (1, 4):
            q = qz.make_quantizer(jax.random.PRNGKey(8), op.m, f"{bits}bit")
            e_x = eng_mod.SketchEngine(op, "xla", quantizer=q)
            e_p = eng_mod.SketchEngine(op, "pallas", block_n=64, quantizer=q)
            s_x = e_x.update(e_x.init_state(), x)
            s_p = e_p.update(e_p.init_state(), x)
            assert bool(jnp.array_equal(s_x.qcos_acc, s_p.qcos_acc)), bits
            assert bool(jnp.array_equal(s_x.qsin_acc, s_p.qsin_acc)), bits


class TestDtypeSatellite:
    def test_draw_frequencies_dtype(self):
        w32 = fq.draw_frequencies(jax.random.PRNGKey(0), 16, 4, 1.0)
        assert w32.dtype == jnp.float32
        with jax.enable_x64(True):
            w64 = fq.draw_frequencies(
                jax.random.PRNGKey(0), 16, 4, 1.0, dtype=jnp.float64
            )
            assert w64.dtype == jnp.float64

    def test_radius_inverse_cdf_f32_f64_agree(self):
        """On identical uniforms, the f32 and f64 grid samplers agree to f32
        resolution — the CDF accumulation is not precision-fragile."""
        u = np.linspace(0.005, 0.995, 199)
        for sigma2 in (0.25, 1.0, 9.0):
            r32 = np.asarray(fq.radius_from_uniform(u, sigma2, jnp.float32))
            with jax.enable_x64(True):
                r64 = np.asarray(
                    fq.radius_from_uniform(u, sigma2, jnp.float64)
                )
            np.testing.assert_allclose(r32, r64, rtol=2e-4, atol=1e-6)

    def test_zero_uniform_draws_no_zero_frequency(self):
        """Regression: a radius uniform of exactly 0 (PRNGKey(15642)'s dense
        draw of 1000 has one) mapped to a zero frequency, which has no
        direction and sketches every point to 1.  It now takes the middle
        of its 2^-23 bin, below every other draw's radius (the structured
        operator's radii come from the same sampler)."""
        r = np.asarray(fq.radius_from_uniform(np.array([0.0, 2.0**-23]), 1.0))
        assert 0 < r[0] < r[1]
        w = fq.draw_frequencies(jax.random.PRNGKey(15642), 1000, 10, 1.0)
        assert float(jnp.min(jnp.linalg.norm(w, axis=0))) > 0

    def test_ckm_config_propagates_dtype(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (256, 3))
        cfg = ckm_mod.CKMConfig(k=2, m=24, sigma2=1.0, freq_dtype="float32")
        z, op, _, _ = ckm_mod.compute_sketch(jax.random.PRNGKey(1), x, cfg)
        assert op.materialize().dtype == jnp.float32
        assert op.spec().dtype == "float32"

    @pytest.mark.parametrize("freq_op", ["dense", "structured"])
    def test_f64_operator_fits_end_to_end(self, freq_op):
        """An f64 operator projects in f64 but the sketch/decoder pipeline
        keeps its f32 accumulator contract — the advertised
        ``freq_dtype="float64"`` path must actually fit."""
        with jax.enable_x64(True):
            x = jax.random.normal(jax.random.PRNGKey(0), (256, 3), jnp.float32)
            cfg = ckm_mod.CKMConfig(
                k=2, m=24, sigma2=1.0, freq_op=freq_op, freq_dtype="float64",
                atom_steps=5, joint_steps=5, nnls_iters=5, final_steps=5,
            )
            res = ckm_mod.fit(jax.random.PRNGKey(1), x, cfg)
            assert res.freq_op.materialize().dtype == jnp.float64
            assert res.sketch.dtype == jnp.float32
            assert np.all(np.isfinite(np.asarray(res.centroids)))


class TestSigma2Estimation:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_recovers_scale_within_2x(self, seed):
        """Satellite: the small-sketch regression heuristic lands within 2x
        of the true within-cluster sigma^2 on Gaussian blobs (k=3, n=4,
        separation c=6), across seeds and cluster scales."""
        from repro.data import synthetic

        x, _, _ = synthetic.gaussian_mixture(
            jax.random.PRNGKey(seed), 4000, k=3, n=4, c=6.0, return_labels=True
        )
        for scale in (0.5, 2.0):
            true_s2 = scale * scale  # unit clusters scaled by `scale`
            est = float(
                fq.estimate_sigma2(jax.random.PRNGKey(seed + 100), x * scale)
            )
            assert 0.5 * true_s2 <= est <= 2.0 * true_s2, (seed, scale, est)


@pytest.mark.slow
class TestStructuredEndToEnd:
    def test_structured_fit_recovers_blobs(self, gaussian_blobs):
        """The structured family localises every true mean like dense fit."""
        x, _, means = gaussian_blobs
        cfg = ckm_mod.CKMConfig(k=5, freq_op="structured")
        res = ckm_mod.fit(jax.random.PRNGKey(0), x, cfg)
        assert isinstance(res.freq_op, fo.StructuredOperator)
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

    def test_structured_quantized_streaming(self, gaussian_blobs):
        """Composes with QCKM + fit_streaming (one-pass, both decoders)."""
        from repro.data import pipeline as pipe

        x, _, _ = gaussian_blobs
        cfg = ckm_mod.CKMConfig(
            k=5, freq_op="structured", sketch_quantization="1bit",
            decoder="sketch_shift", shift_steps=40, shift_polish_steps=150,
            nnls_iters=60,
        )
        res = ckm_mod.fit_streaming(
            jax.random.PRNGKey(2), pipe.chunked(x, 1000), cfg
        )
        sse_rel = float(ckm_mod.sse(x, res.centroids)) / x.shape[0]
        assert np.isfinite(sse_rel)
        # Well below the dataset variance — the decode genuinely worked.
        assert sse_rel < 2.0 * 4.0  # n=4 unit-variance clusters
