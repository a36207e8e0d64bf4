"""The sketching operator ``Sk`` / ``A`` (paper §3.1), in JAX.

The sketch of weighted points ``(Y, beta)`` at frequencies ``W = [w_1..w_m]`` is

    Sk(Y, beta)_j = sum_l beta_l * exp(-i w_j^T y_l)          (complex, length m)

Internally everything uses the *stacked-real* representation

    z = [ sum_l beta_l cos(Y W) ,  -sum_l beta_l sin(Y W) ]   (real, length 2m)

because (a) TPUs have no complex MXU path, (b) autodiff and Pallas kernels are
simpler on reals, and (c) the l2 norm is preserved:  |z_complex|^2 == |z_real|^2.

Every atom ``A delta_c`` has constant modulus 1 per frequency, hence constant
norm ``||A delta_c||_2 = sqrt(m)`` — used by CLOMPR's normalised correlation step.

Frequency-operator contract: every function here takes ``w`` as either a
``core.freq_ops.FrequencyOperator`` (the registry object — projections via
``op.apply``, which is a fast transform for the structured family) or a raw
``(n, m)`` array, wrapped silently in a ``"dense"`` operator for convenience
(``x @ w`` numerics are bitwise-unchanged).  The decoder helpers and kernel
wrappers are stricter — they raise ``TypeError`` on raw arrays (PR 6).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import freq_ops as fo
from repro.utils import compat

# f32-exact contractions on the TPU, whose default f32 matmul is one bf16 pass.
_HI = jax.lax.Precision.HIGHEST

__all__ = [
    "sketch",
    "sketch_quantized",
    "sketch_complex",
    "to_complex",
    "from_complex",
    "atom",
    "atoms",
    "atom_norm",
    "data_bounds",
]


def _stacked(cos_part: jax.Array, sin_part: jax.Array) -> jax.Array:
    return jnp.concatenate([cos_part, -sin_part], axis=-1)


def to_complex(z: jax.Array) -> jax.Array:
    """Stacked-real (…, 2m) -> complex (…, m)."""
    m = z.shape[-1] // 2
    return jax.lax.complex(z[..., :m], z[..., m:])


def from_complex(zc: jax.Array) -> jax.Array:
    """Complex (…, m) -> stacked-real (…, 2m)."""
    return jnp.concatenate([jnp.real(zc), jnp.imag(zc)], axis=-1)


@functools.partial(jax.jit, static_argnames=("chunk", "vary_axes"))
def sketch(
    x: jax.Array,
    w: jax.Array,
    weights: jax.Array | None = None,
    chunk: int = 8192,
    vary_axes: tuple[str, ...] = (),
) -> jax.Array:
    """Sketch of points ``x: (N, n)`` at frequencies ``w: (n, m)``.

    Returns the stacked-real sketch ``(2m,)``.  ``weights`` defaults to uniform
    ``1/N``.  Computation is chunked over N with an f32 accumulator so the
    ``(N, m)`` projection matrix never fully materialises.

    ``vary_axes``: when called inside ``shard_map`` on per-device shards, the
    scan carry must be marked as varying over the manual mesh axes.
    """
    op = fo.as_operator(w)
    x = jnp.asarray(x, jnp.float32)
    n_pts = x.shape[0]
    m = op.m
    if weights is None:
        weights = jnp.full((n_pts,), 1.0 / n_pts, jnp.float32)
    else:
        weights = jnp.asarray(weights, jnp.float32)

    pad = (-n_pts) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
        weights = jnp.concatenate([weights, jnp.zeros((pad,), weights.dtype)], axis=0)
    n_chunks = x.shape[0] // chunk
    xs = x.reshape(n_chunks, chunk, -1)
    ws_ = weights.reshape(n_chunks, chunk)

    def body(acc, inp):
        xc, bc = inp
        # Accumulators are f32 regardless of the operator's sampling dtype
        # (an f64 operator projects in f64; the cast is a no-op for f32 ops).
        proj = jnp.asarray(op.apply(xc), jnp.float32)  # (chunk, m)
        c = jnp.dot(bc, jnp.cos(proj), precision=_HI)  # (m,)
        s = jnp.dot(bc, jnp.sin(proj), precision=_HI)
        return (acc[0] + c, acc[1] + s), None

    acc0 = jnp.zeros((m,), jnp.float32)
    if vary_axes:
        acc0 = compat.pvary(acc0, vary_axes)
    (cos_acc, sin_acc), _ = jax.lax.scan(body, (acc0, acc0), (xs, ws_))
    return _stacked(cos_acc, sin_acc)


@functools.partial(jax.jit, static_argnames=("bits", "chunk", "vary_axes"))
def sketch_quantized(
    x: jax.Array,
    w: jax.Array,
    dither: jax.Array,
    valid: jax.Array | None = None,
    bits: int = 1,
    chunk: int = 8192,
    vary_axes: tuple[str, ...] = (),
) -> tuple[jax.Array, jax.Array]:
    """Universally-quantized sketch sums (QCKM) — the XLA fallback path.

    Returns int32 ``(q_cos_sum, q_sin_sum)`` of shape ``(m,)``: the per-point
    codes ``quantize.quantize_codes(x @ w, dither, bits)`` summed over N.
    Deterministic per point (the dither is per-frequency), hence exactly
    split-invariant; chunked over N like :func:`sketch` so the ``(N, m)``
    projection never materialises.  ``valid`` is a 0/1 row mask for padding
    (masked rows contribute zero codes).  ``vary_axes``: see :func:`sketch`.
    """
    from repro.core import quantize as qz

    op = fo.as_operator(w)
    x = jnp.asarray(x, jnp.float32)
    n_pts = x.shape[0]
    m = op.m
    if valid is None:
        valid = jnp.ones((n_pts,), jnp.float32)
    else:
        valid = jnp.asarray(valid, jnp.float32)

    pad = (-n_pts) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
        valid = jnp.concatenate([valid, jnp.zeros((pad,), valid.dtype)], axis=0)
    n_chunks = x.shape[0] // chunk
    xs = x.reshape(n_chunks, chunk, -1)
    vs = valid.reshape(n_chunks, chunk)

    if isinstance(op, fo.DenseOperator):
        # The fused kernel's phase arithmetic, so 1-bit codes match it bitwise.
        from repro.kernels.fourier_sketch import split_matmul

        def project(xc):
            return split_matmul(xc, op.w.astype(jnp.float32))
    else:
        def project(xc):  # f32 phases (see sketch)
            return jnp.asarray(op.apply(xc), jnp.float32)

    def body(acc, inp):
        xc, vc = inp
        qc, qs = qz.quantize_codes(project(xc), dither, bits, valid=vc[:, None])
        return (acc[0] + jnp.sum(qc, axis=0), acc[1] + jnp.sum(qs, axis=0)), None

    acc0 = jnp.zeros((m,), jnp.int32)
    if vary_axes:
        acc0 = compat.pvary(acc0, vary_axes)
    (qcos, qsin), _ = jax.lax.scan(body, (acc0, acc0), (xs, vs))
    return qcos, qsin


def sketch_complex(
    x: jax.Array, w: jax.Array, weights: jax.Array | None = None, chunk: int = 8192
) -> jax.Array:
    """Complex view of :func:`sketch` — matches the paper's ``Sk(Y, beta)``."""
    return to_complex(sketch(x, w, weights, chunk))


def atom(c: jax.Array, w: jax.Array) -> jax.Array:
    """``A delta_c`` for a single centroid ``c: (n,)`` -> stacked-real ``(2m,)``."""
    proj = jnp.asarray(fo.as_operator(w).apply(c), jnp.float32)  # (m,)
    return _stacked(jnp.cos(proj), jnp.sin(proj))


def atoms(cs: jax.Array, w: jax.Array) -> jax.Array:
    """``A delta_c`` for centroids ``cs: (S, n)`` -> ``(S, 2m)``."""
    proj = jnp.asarray(fo.as_operator(w).apply(cs), jnp.float32)  # (S, m)
    return _stacked(jnp.cos(proj), jnp.sin(proj))


def atom_norm(m: int) -> float:
    """||A delta_c||_2 — constant: every frequency sample has modulus 1."""
    return float(jnp.sqrt(m))


@jax.jit
def data_bounds(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-coordinate bounds ``l <= x_i <= u`` — same single pass as the sketch."""
    return jnp.min(x, axis=0), jnp.max(x, axis=0)
