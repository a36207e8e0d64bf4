"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import freq_ops as fo
from repro.kernels import ops, ref


def _data(seed, n_pts, feat, m):
    key = jax.random.PRNGKey(seed)
    kx, kw, kb = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n_pts, feat), jnp.float32) * 2.0
    w = jax.random.normal(kw, (feat, m), jnp.float32)
    beta = jax.random.uniform(kb, (n_pts,), jnp.float32)
    return x, w, beta


class TestFourierSketchKernel:
    @pytest.mark.parametrize(
        "n_pts,feat,m",
        [
            (128, 8, 128),  # exactly aligned
            (100, 10, 130),  # ragged everywhere
            (1, 3, 7),  # degenerate small
            (2048, 16, 512),  # multiple grid steps both axes
            (513, 1, 1),  # single feature / frequency
            (333, 24, 257),
        ],
    )
    def test_matches_ref(self, n_pts, feat, m):
        x, w, beta = _data(0, n_pts, feat, m)
        z = ops.fourier_sketch(
            x, fo.as_operator(w), beta, block_n=128, block_m=128, interpret=True
        )
        cos_ref, sin_ref = ref.fourier_sketch_ref(x, w, beta)
        np.testing.assert_allclose(np.asarray(z[:m]), np.asarray(cos_ref), atol=1e-4)
        np.testing.assert_allclose(np.asarray(z[m:]), np.asarray(-sin_ref), atol=1e-4)

    def test_matches_core_sketch(self):
        """Kernel is a drop-in for core.sketch.sketch (same stacked-real)."""
        from repro.core import sketch as sk

        x, w, _ = _data(1, 400, 6, 64)
        z_kernel = ops.fourier_sketch(
            x, fo.as_operator(w), interpret=True, block_n=128, block_m=128
        )
        z_core = sk.sketch(x, w)
        np.testing.assert_allclose(np.asarray(z_kernel), np.asarray(z_core), atol=1e-4)

    @pytest.mark.parametrize("block_n,block_m", [(8, 128), (64, 128), (256, 512)])
    def test_block_shape_invariance(self, block_n, block_m):
        x, w, beta = _data(2, 300, 12, 200)
        z = ops.fourier_sketch(
            x, fo.as_operator(w), beta, block_n=block_n, block_m=block_m,
            interpret=True,
        )
        cos_ref, sin_ref = ref.fourier_sketch_ref(x, w, beta)
        np.testing.assert_allclose(np.asarray(z[:200]), np.asarray(cos_ref), atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_input_dtypes(self, dtype):
        """Inputs in bf16 are upcast to f32 accumulate in the wrapper."""
        x, w, beta = _data(3, 256, 8, 128)
        z = ops.fourier_sketch(
            x.astype(dtype), fo.as_operator(w.astype(dtype)), beta,
            interpret=True, block_n=128, block_m=128,
        )
        cos_ref, _ = ref.fourier_sketch_ref(x.astype(dtype), w.astype(dtype), beta)
        atol = 1e-4 if dtype == jnp.float32 else 0.3
        np.testing.assert_allclose(np.asarray(z[:128]), np.asarray(cos_ref), atol=atol)


class TestSplitMatmul:
    """The quantized encoder's phase matmul: f32-accurate from one bf16
    pass, and the in-kernel and XLA roundings give the same bits."""

    @pytest.mark.parametrize("feat", [1, 10, 16])
    @pytest.mark.parametrize("in_kernel", [False, True])
    def test_f32_accurate(self, feat, in_kernel):
        from repro.kernels.fourier_sketch import split_matmul

        x, w, _ = _data(4, 512, feat, 200)
        got = np.asarray(jax.jit(
            lambda a, b: split_matmul(a, b, in_kernel=in_kernel)
        )(x, w), np.float64)
        want = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
        # f32 rounding of |phases| up to ~30 rad, far below a bf16 pass's.
        assert np.max(np.abs(got - want)) < 2e-5

    def test_kernel_and_xla_roundings_agree_bitwise(self):
        from repro.kernels.fourier_sketch import split_matmul

        x, w, _ = _data(5, 512, 10, 200)
        a = jax.jit(lambda a, b: split_matmul(a, b))(x, w)
        b = jax.jit(lambda a, b: split_matmul(a, b, in_kernel=True))(x, w)
        assert bool(jnp.array_equal(a, b))


class TestAssignArgminKernel:
    @pytest.mark.parametrize(
        "n_pts,feat,k",
        [
            (128, 8, 8),
            (100, 10, 10),  # ragged
            (1, 4, 3),
            (2048, 16, 64),
            (777, 5, 13),
        ],
    )
    def test_matches_ref(self, n_pts, feat, k):
        key = jax.random.PRNGKey(10)
        kx, kc = jax.random.split(key)
        x = jax.random.normal(kx, (n_pts, feat)) * 3
        c = jax.random.normal(kc, (k, feat)) * 3
        idx, dist = ops.assign_argmin(x, c, block_n=128, interpret=True)
        idx_ref, dist_ref = ref.assign_argmin_ref(x, c)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
        np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_ref), atol=1e-3)

    def test_matches_lloyd_assign(self):
        """Kernel agrees with the Lloyd-Max internal assignment."""
        from repro.core.lloyd import _assign

        key = jax.random.PRNGKey(11)
        kx, kc = jax.random.split(key)
        x = jax.random.normal(kx, (500, 6))
        c = jax.random.normal(kc, (9, 6))
        idx, dist = ops.assign_argmin(x, c, interpret=True)
        idx_ref, dist_ref = _assign(x, c)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
        np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_ref), atol=1e-3)

    def test_ties_resolve_to_lowest_index(self):
        """argmin tie-breaking must match jnp (first minimum wins)."""
        x = jnp.zeros((16, 4))
        c = jnp.zeros((5, 4))  # all centroids identical -> all ties
        idx, _ = ops.assign_argmin(x, c, interpret=True)
        np.testing.assert_array_equal(np.asarray(idx), np.zeros(16, np.int32))


class TestSketchShiftKernel:
    def _problem(self, seed, p_cand, feat, m):
        key = jax.random.PRNGKey(seed)
        kc, kw, kz = jax.random.split(key, 3)
        c = jax.random.normal(kc, (p_cand, feat)) * 2.0
        w = jax.random.normal(kw, (feat, m)) * 0.7
        z = jax.random.normal(kz, (2 * m,)) * 0.3
        return c, fo.as_operator(w), z

    @pytest.mark.parametrize(
        "p_cand,feat,m",
        [
            (8, 8, 128),  # exactly aligned
            (37, 5, 300),  # ragged everywhere
            (1, 2, 7),  # degenerate small
            (40, 4, 200),  # the decoder's default swarm shape
        ],
    )
    def test_pallas_matches_ref(self, p_cand, feat, m):
        c, w, z = self._problem(0, p_cand, feat, m)
        f, g = ops.sketch_shift_scores(
            c, w, z, impl="pallas", block_p=8, block_m=128, interpret=True
        )
        f_ref, g_ref = ref.sketch_shift_scores_ref(c, w.materialize(), z)
        np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), atol=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5)

    def test_xla_matches_ref(self):
        """The decoder's default impl vs the complex-arithmetic oracle."""
        c, w, z = self._problem(1, 25, 6, 250)
        f, g = ops.sketch_shift_scores(c, w, z, impl="xla")
        f_ref, g_ref = ref.sketch_shift_scores_ref(c, w.materialize(), z)
        np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), atol=1e-5)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-5)

    def test_gradient_is_density_gradient(self):
        """g must be the autodiff gradient of f (the op returns both fused)."""
        c, w, z = self._problem(2, 6, 4, 96)

        def f_single(ci):
            f, _ = ops.sketch_shift_scores(ci[None, :], w, z, impl="xla")
            return f[0]

        g_auto = jax.vmap(jax.grad(f_single))(c)
        _, g = ops.sketch_shift_scores(c, w, z, impl="xla")
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_auto), atol=1e-5)

    def test_unknown_impl_raises(self):
        c, w, z = self._problem(3, 4, 3, 64)
        with pytest.raises(ValueError, match="impl"):
            ops.sketch_shift_scores(c, w, z, impl="cuda")


class TestFlashAttentionKernel:
    @pytest.mark.parametrize(
        "b,s,h,kv,hd,causal,window",
        [
            (1, 128, 4, 4, 32, True, 0),     # MHA causal
            (2, 128, 4, 2, 32, True, 0),     # GQA rep=2
            (1, 256, 4, 1, 32, True, 64),    # MQA + sliding window
            (1, 96, 2, 2, 16, True, 0),      # ragged seq (padding path)
            (1, 128, 2, 2, 32, False, 0),    # non-causal (encoder)
        ],
    )
    def test_matches_ref(self, b, s, h, kv, hd, causal, window):
        key = jax.random.PRNGKey(0)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, s, h, hd), jnp.float32)
        k = jax.random.normal(kk, (b, s, kv, hd), jnp.float32)
        v = jax.random.normal(kv_, (b, s, kv, hd), jnp.float32)
        out = ops.flash_attention(
            q, k, v, causal=causal, window=window,
            block_q=64, block_k=64, interpret=True,
        )
        rep = h // kv
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
        kf = k.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)
        vf = v.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)
        expect = ref.flash_attention_ref(qf, kf, vf, rep, causal, window)
        expect = expect.reshape(b, h, s, hd).transpose(0, 2, 1, 3).reshape(b, s, h * hd)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(expect), atol=2e-3, rtol=1e-2
        )

    def test_matches_model_attention(self):
        """Flash output == the model's q-chunked XLA attention (post-rope)."""
        from repro.models import layers as L

        dims = L.AttnDims(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                          q_block=32)
        params = L.init_attention(jax.random.PRNGKey(0), dims)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
        ref_out = L.attention_apply(params, dims, x, pos)
        q, k, v = L._qkv(params, dims, x, pos)
        flash = ops.flash_attention(q, k, v, causal=True, block_q=32,
                                    block_k=32, interpret=True)
        flash = flash @ params["wo"]
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(ref_out), atol=2e-3, rtol=1e-2
        )

    def test_bf16(self):
        key = jax.random.PRNGKey(2)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, 128, 2, 32), jnp.bfloat16)
        k = jax.random.normal(kk, (1, 128, 2, 32), jnp.bfloat16)
        v = jax.random.normal(kv_, (1, 128, 2, 32), jnp.bfloat16)
        out = ops.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
        assert out.dtype == jnp.bfloat16
        qf = q.transpose(0, 2, 1, 3).reshape(2, 128, 32)
        expect = ref.flash_attention_ref(
            qf,
            k.transpose(0, 2, 1, 3).reshape(2, 128, 32),
            v.transpose(0, 2, 1, 3).reshape(2, 128, 32),
            1, True, 0,
        )
        np.testing.assert_allclose(
            np.asarray(out[0]).reshape(128, 2, 32).transpose(1, 0, 2).astype(np.float32),
            np.asarray(expect).astype(np.float32),
            atol=3e-2, rtol=3e-2,
        )
