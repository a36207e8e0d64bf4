"""Jit'd public wrappers around the Pallas kernels (see ``docs/api.md``).

Entry points
------------
- ``fourier_sketch_sums`` / ``fourier_sketch`` — fused float RFF sketch, the
  ``pallas`` backend of ``core.engine.SketchEngine``;
- ``quantized_fourier_sketch_sums`` — fused QCKM encoder: dithered phases ->
  integer sign / b-bit codes accumulated in int32 (``core.quantize``);
- ``sketch_shift_scores`` — density + gradient of the sketched characteristic
  function, the inner score/shift step of the ``sketch_shift`` decoder
  (``core.decoders.sketch_shift``); ``impl="xla" | "pallas"`` mirrors the
  sketch side's backend treatment;
- ``amp_denoise`` — truncated-Gaussian posterior moments over K centroid
  estimates, the input-channel denoiser of the ``amp`` decoder
  (``core.decoders.amp``); same ``impl="xla" | "pallas"`` dispatch;
- ``flash_attention`` — fused attention forward for the serving path;
- ``assign_argmin`` — fused nearest-centroid assignment.

Handles padding/alignment (lane width 128, sublane 8, block divisibility) and
backend dispatch: on TPU the compiled kernels run natively; on CPU (this
container) they run in ``interpret=True`` mode, which executes the kernel body
in Python for correctness validation.  Padded regions are constructed so they
cannot perturb results (zero weights / zero valid-masks, +inf distances), and
outputs are sliced back to logical shapes.

Frequency operators: the sketch-side ops take ``w`` as a
``core.freq_ops.FrequencyOperator``; raw ``(n, m)`` arrays are a
``TypeError`` since the one-release deprecation window closed (wrap with
``freq_ops.as_operator``).  Dispatch is per family: ``"dense"`` runs the original fused
matmul+trig kernels (``kernels/fourier_sketch.py``, bitwise-unchanged),
``"structured"`` runs the fused WHT-chain kernels
(``kernels/freq_transform.py``), and any user-registered operator falls back
to the chunked XLA path through ``op.apply`` (correct everywhere, unfused).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import assign_argmin as _assign
from repro.kernels import fourier_sketch as _sketch


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(a: jax.Array, axis: int, mult: int, value: float = 0.0) -> jax.Array:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _as_op(w):
    from repro.core import freq_ops

    if not isinstance(w, freq_ops.FrequencyOperator):
        raise TypeError(
            "kernels.ops sketch-side entry points require a "
            "core.freq_ops.FrequencyOperator; the raw (n, m) array path was "
            "removed after its one-release deprecation window (PR 5) — wrap "
            "with freq_ops.as_operator(w) or build one via "
            "freq_ops.make_operator(...)"
        )
    return w


def _structured_block_n(block_n, op):
    """Batch tile of the structured kernels: at most 2^17 elements per
    ``(block_n, d)`` tile, so the tile, ``H_d`` and the chain's temporaries
    stay inside the kernel's VMEM at wide blocks (d = 512 and up)."""
    return min(block_n, max(8, (1 << 17) // op.d))


def _structured_pad(x, op, block_n):
    """Pad a batch for the structured kernels: N to block, n to the WHT width
    (zero feature columns shift no phases — the operator itself zero-pads)."""
    x = _pad_to(jnp.asarray(x, jnp.float32), 0, block_n)
    return _pad_to(x, 1, op.d)[:, : op.d]


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def fourier_sketch_sums(
    x: jax.Array,
    w: jax.Array,
    beta: jax.Array,
    block_n: int = 1024,
    block_m: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Raw fused sums ``(sum b cos(xW) (m,), sum b sin(xW) (m,))``.

    The mergeable-state entrypoint used by ``core.engine`` (pallas backend):
    no ``1/N`` normalisation, no stacked-real packaging.  Handles all TPU
    padding/alignment; off-TPU the kernels run in interpret mode.  ``w`` is a
    frequency operator (or raw matrix): dense -> the fused matmul kernel,
    structured -> the fused WHT-chain kernel, other registered families ->
    the chunked XLA fallback through ``op.apply``.
    """
    from repro.core import freq_ops
    from repro.core import sketch as core_sk

    if interpret is None:
        interpret = _on_cpu()
    op = _as_op(w)
    n_pts = x.shape[0]
    m = op.m
    x = jnp.asarray(x, jnp.float32)
    beta = jnp.asarray(beta, jnp.float32).reshape(-1, 1)
    block_n = min(block_n, max(8, 1 << (n_pts - 1).bit_length()))

    if isinstance(op, freq_ops.StructuredOperator):
        from repro.kernels import freq_transform as _ft

        block_n = _structured_block_n(block_n, op)
        xp = _structured_pad(x, op, block_n)
        beta_p = _pad_to(beta, 0, block_n)  # zero-weight rows are no-ops
        cos_s, sin_s = _ft.structured_sketch_kernel(
            xp, jnp.asarray(op.diags, jnp.float32),
            jnp.asarray(op.radii, jnp.float32), beta_p, block_n=block_n,
            interpret=interpret,
        )
        return cos_s.reshape(-1)[:m], sin_s.reshape(-1)[:m]
    if not isinstance(op, freq_ops.DenseOperator):
        # User-registered operator family: no fused kernel — chunked XLA path
        # through op.apply (same mergeable-sums contract).
        part = core_sk.sketch(
            x, op, weights=beta.reshape(-1), chunk=min(8192, max(n_pts, 1))
        )
        return part[:m], -part[m:]

    w = jnp.asarray(op.w, jnp.float32)
    block_m = min(block_m, max(128, 1 << (m - 1).bit_length()))
    # Pad: N to block (zero weight rows are no-ops), n to sublane multiple
    # (zero feature columns shift no phases), m to block (sliced off below).
    x = _pad_to(_pad_to(x, 0, block_n), 1, 8)
    beta = _pad_to(beta, 0, block_n)
    w = _pad_to(_pad_to(w, 0, 8), 1, block_m)
    cos_s, sin_s = _sketch.fourier_sketch_kernel(
        x, w, beta, block_n=block_n, block_m=block_m, interpret=interpret
    )
    return cos_s[0, :m], sin_s[0, :m]


@functools.partial(
    jax.jit, static_argnames=("bits", "block_n", "block_m", "interpret")
)
def quantized_fourier_sketch_sums(
    x: jax.Array,
    w: jax.Array,
    dither: jax.Array,
    valid: jax.Array | None = None,
    bits: int = 1,
    block_n: int = 1024,
    block_m: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused QCKM encoder: int32 ``(q_cos_sums (m,), q_sin_sums (m,))``.

    The quantized mergeable-state entrypoint used by ``core.engine`` (pallas
    backend with a ``quantizer``): per point, quantize the dithered phase
    ``w^T x + xi`` to a 1-bit sign (``bits=1``) or ``b``-bit uniform code and
    accumulate integer sums — the XLA twin is ``core.sketch.sketch_quantized``.
    Padding rows carry ``valid=0`` so they contribute zero codes.
    """
    from repro.core import freq_ops
    from repro.core import sketch as core_sk
    from repro.kernels import fourier_sketch as _qsk

    if interpret is None:
        interpret = _on_cpu()
    op = _as_op(w)
    n_pts = x.shape[0]
    m = op.m
    x = jnp.asarray(x, jnp.float32)
    if valid is None:
        valid = jnp.ones((n_pts,), jnp.float32)
    valid = jnp.asarray(valid, jnp.float32).reshape(-1, 1)
    block_n = min(block_n, max(8, 1 << (n_pts - 1).bit_length()))

    if isinstance(op, freq_ops.StructuredOperator):
        from repro.kernels import freq_transform as _ft

        block_n = _structured_block_n(block_n, op)
        xp = _structured_pad(x, op, block_n)
        valid_p = _pad_to(valid, 0, block_n)  # valid=0 rows -> zero codes
        # Dither padded to the block tail with zeros (tail codes sliced off).
        dth = _pad_to(
            jnp.asarray(dither, jnp.float32).reshape(1, -1), 1,
            op.nblocks * op.d,
        ).reshape(op.nblocks, op.d)
        qcos, qsin = _ft.quantized_structured_sketch_kernel(
            xp, jnp.asarray(op.diags, jnp.float32),
            jnp.asarray(op.radii, jnp.float32), dth, valid_p,
            bits=bits, block_n=block_n,
            interpret=interpret,
        )
        return qcos.reshape(-1)[:m], qsin.reshape(-1)[:m]
    if not isinstance(op, freq_ops.DenseOperator):
        return core_sk.sketch_quantized(
            x, op, jnp.asarray(dither, jnp.float32),
            valid=valid.reshape(-1), bits=bits,
            chunk=min(8192, max(n_pts, 1)),
        )

    w = jnp.asarray(op.w, jnp.float32)
    dither = jnp.asarray(dither, jnp.float32).reshape(1, -1)
    block_m = min(block_m, max(128, 1 << (m - 1).bit_length()))
    # Pad: N to block (valid=0 rows contribute zero codes), n to sublane
    # multiple (zero feature columns shift no phases), m to block (sliced off).
    x = _pad_to(_pad_to(x, 0, block_n), 1, 8)
    valid = _pad_to(valid, 0, block_n)
    w = _pad_to(_pad_to(w, 0, 8), 1, block_m)
    dither = _pad_to(dither, 1, block_m)
    qcos, qsin = _qsk.quantized_fourier_sketch_kernel(
        x,
        w,
        dither,
        valid,
        bits=bits,
        block_n=block_n,
        block_m=block_m,
        interpret=interpret,
    )
    return qcos[0, :m], qsin[0, :m]


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def fourier_sketch(
    x: jax.Array,
    w: jax.Array,
    beta: jax.Array | None = None,
    block_n: int = 1024,
    block_m: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused sketch -> stacked-real ``(2m,)``: [sum b cos(xW), -sum b sin(xW)].

    Drop-in replacement for ``core.sketch.sketch`` (same convention).  ``beta``
    defaults to uniform ``1/N``.
    """
    if beta is None:
        beta = jnp.full((x.shape[0],), 1.0 / x.shape[0], jnp.float32)
    cos_s, sin_s = fourier_sketch_sums(
        x, w, beta, block_n=block_n, block_m=block_m, interpret=interpret
    )
    return jnp.concatenate([cos_s, -sin_s])


@functools.partial(
    jax.jit, static_argnames=("impl", "block_p", "block_m", "interpret")
)
def sketch_shift_scores(
    c: jax.Array,
    w: jax.Array,
    z: jax.Array,
    impl: str = "xla",
    block_p: int = 256,
    block_m: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Sketched-density score + gradient at candidate centroids ``c: (P, n)``.

    The inner step of the sketch-and-shift decoder: for the stacked-real
    sketch ``z = [z1, z2]`` (``(2m,)``) and frequencies ``w: (n, m)`` returns

        f(c)  = (1/m) Σ_j [cos(w_j·c) z1_j - sin(w_j·c) z2_j]     -> (P,)
        ∇f(c) = (1/m) Σ_j w_j [-sin(w_j·c) z1_j - cos(w_j·c) z2_j] -> (P, n)

    which is a kernel-density surrogate of the data distribution (``f(c) =
    Σ_l β_l κ(c - x_l)`` with κ the frequency distribution's characteristic
    kernel) — mean-shift iterations ascend it.  ``impl`` selects the same two
    treatments the sketch side gets: ``"xla"`` (plain fused jnp through the
    operator's ``apply``/``adjoint`` — a fast transform for the structured
    family; runs anywhere — the default) or ``"pallas"`` (the fused
    VMEM-resident TPU kernel ``kernels.sketch_shift``; interpret mode
    off-TPU; non-dense operators are materialised for this kernel, so prefer
    ``"xla"`` with the structured family).
    """
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown sketch_shift impl {impl!r}")
    op = _as_op(w)
    c = jnp.asarray(c, jnp.float32)
    z = jnp.asarray(z, jnp.float32)
    m = op.m
    z1, z2 = z[:m], z[m:]
    if impl == "xla":
        proj = jnp.asarray(op.apply(c), jnp.float32)  # (P, m)
        cosp, sinp = jnp.cos(proj), jnp.sin(proj)
        hi = jax.lax.Precision.HIGHEST
        f = (jnp.dot(cosp, z1, precision=hi) - jnp.dot(sinp, z2, precision=hi)) / m
        g = jnp.asarray(
            op.adjoint((-sinp) * z1[None, :] - cosp * z2[None, :]), jnp.float32
        ) / m
        return f, g
    if interpret is None:
        interpret = _on_cpu()
    from repro.kernels import sketch_shift as _shift

    w = jnp.asarray(op.materialize(), jnp.float32)
    p_cand, feat = c.shape
    block_p = min(block_p, max(8, 1 << (p_cand - 1).bit_length()))
    block_m = min(block_m, max(128, 1 << (m - 1).bit_length()))
    # Pad: P to block (garbage rows sliced off), n to sublane multiple (zero
    # feature columns shift no phases and add zero gradient columns), m to
    # block with zero frequency columns AND zero sketch entries (cos(0)*0
    # contributes nothing to f; zero w columns contribute nothing to grad).
    c_p = _pad_to(_pad_to(c, 0, block_p), 1, 8)
    w_p = _pad_to(_pad_to(w, 0, 8), 1, block_m)
    z1_p = _pad_to(z1.reshape(1, -1), 1, block_m)
    z2_p = _pad_to(z2.reshape(1, -1), 1, block_m)
    f_sums, g_sums = _shift.sketch_shift_kernel(
        c_p, w_p, z1_p, z2_p, block_p=block_p, block_m=block_m,
        interpret=interpret,
    )
    return f_sums[:p_cand, 0] / m, g_sums[:p_cand, :feat] / m


@functools.partial(
    jax.jit, static_argnames=("impl", "block_k", "interpret")
)
def amp_denoise(
    r: jax.Array,
    q: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    impl: str = "xla",
    block_k: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Truncated-Gaussian posterior denoiser over K centroid estimates.

    The input channel of the CL-AMP decoder (``core.decoders.amp``): for the
    pseudo-data matrix ``r: (K, n)`` with scalar pseudo-variance ``q`` and the
    engine's box bounds ``lower/upper: (n,)``, returns the posterior
    ``(mean (K, n), variance (K, n))`` of each coordinate under a uniform box
    prior — the truncated-normal moments.  ``impl`` selects the same two
    treatments the other decoder ops get: ``"xla"`` (plain fused jnp; runs
    anywhere — the default) or ``"pallas"`` (the single-VPU-pass kernel
    ``kernels.amp_denoise``; interpret mode off-TPU).  Hardened edge cases
    (identical across impls and the ``ref.py`` oracle): infinite box edges
    contribute zero boundary terms, and vanishing in-box mass (pseudo-data
    far outside the box) collapses to the nearest edge instead of NaN.
    """
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown amp_denoise impl {impl!r}")
    r = jnp.asarray(r, jnp.float32)
    k_est, feat = r.shape
    q = jnp.maximum(jnp.asarray(q, jnp.float32).reshape(()), 1e-20)
    lo = jnp.broadcast_to(jnp.asarray(lower, jnp.float32), (feat,))
    hi = jnp.broadcast_to(jnp.asarray(upper, jnp.float32), (feat,))
    from repro.kernels import amp_denoise as _amp

    if impl == "xla":
        return _amp.truncated_normal_moments(
            r, q, lo[None, :], hi[None, :], erfc_fn=jax.lax.erfc
        )
    if interpret is None:
        interpret = _on_cpu()
    block_k = min(block_k, max(8, 1 << (k_est - 1).bit_length()))
    # Pad: K to block (garbage rows sliced off), n to the lane width with
    # benign cells (r=0 inside a [-1, 1] box at unit variance cannot produce
    # non-finite intermediates).
    r_p = _pad_to(_pad_to(r, 0, block_k), 1, 128)
    q_p = jnp.broadcast_to(q, (1, r_p.shape[1]))
    lo_p = _pad_to(lo.reshape(1, -1), 1, 128, value=-1.0)
    hi_p = _pad_to(hi.reshape(1, -1), 1, 128, value=1.0)
    mean, var = _amp.amp_denoise_kernel(
        r_p, q_p, lo_p, hi_p, block_k=block_k, interpret=interpret
    )
    return mean[:k_est, :feat], var[:k_est, :feat]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, S_q, H, hd)
    k: jax.Array,  # (B, S_kv, KV, hd)
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused flash attention (forward) — drop-in for the q-chunked XLA path
    of ``models.layers.attention_apply`` at serving/prefill time.

    HBM traffic: Q+K+V+O only (vs O(S^2) score blocks).  GQA handled via the
    kernel's head->kv index map.  Returns (B, S_q, H*hd).
    """
    from repro.kernels import flash_attention as _fa

    if interpret is None:
        interpret = _on_cpu()
    b, s_q, h, hd = q.shape
    kvh = k.shape[2]
    rep = h // kvh
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s_q, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, v.shape[1], hd)
    block_q = min(block_q, max(8, 1 << (s_q - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (k.shape[1] - 1).bit_length()))
    pad_q = (-s_q) % block_q
    pad_k = (-k.shape[1]) % block_k
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded kv positions sit at the causal future: masked out for every
        # real query by the position mask.
        assert causal, "kv padding requires the causal mask"
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    o, _lse = _fa.flash_attention_kernel(
        qf, kf, vf, rep=rep, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    o = o[:, :s_q].reshape(b, h, s_q, hd).transpose(0, 2, 1, 3)
    return o.reshape(b, s_q, h * hd)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def assign_argmin(
    x: jax.Array,
    c: jax.Array,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused nearest-centroid assignment: (labels (N,) i32, min d^2 (N,) f32)."""
    if interpret is None:
        interpret = _on_cpu()
    n_pts = x.shape[0]
    x = jnp.asarray(x, jnp.float32)
    c = jnp.asarray(c, jnp.float32)
    block_n = min(block_n, max(8, 1 << (n_pts - 1).bit_length()))
    # Pad features with zeros (adds the same constant to every distance: the
    # argmin is unchanged and the constant is zero since pads match), pad K
    # with +inf-distance phantom centroids, pad N to block.
    x = _pad_to(_pad_to(x, 0, block_n), 1, 8)
    c = _pad_to(c, 1, 8)
    k = c.shape[0]
    pad_k = (-k) % 8
    if pad_k:
        # Phantom centroids far away: never win the argmin.
        far = jnp.full((pad_k, c.shape[1]), 1e18, c.dtype)
        c = jnp.concatenate([c, far], axis=0)
    idx, dist = _assign.assign_argmin_kernel(x, c, block_n=block_n, interpret=interpret)
    return idx[:n_pts], dist[:n_pts]
