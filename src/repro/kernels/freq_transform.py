"""Fast structured frequency transform — WHT building block + fused kernels.

The structured frequency operator (``core.freq_ops.structured``) replaces the
dense ``(n, m)`` frequency matrix with stacked HD-Rademacher blocks: each
block of ``d = 2^ceil(log2 n)`` frequencies is

    B = c·H D_2 · c·H D_1 · c·H D_0          (c = d^{-1/2}, D_i Rademacher)

— an *exactly orthogonal* direction matrix (product of orthogonal factors)
whose rows get adapted-radius radial rescaling.  Projecting a point costs
three Walsh–Hadamard transforms instead of a ``(n, m)`` matvec.

WHT implementation: the Sylvester Hadamard matrix factorises as a Kronecker
product ``H_d = H_a ⊗ H_b`` (``a·b = d``, ``a, b ~ sqrt(d)``), so the
transform is two small dense contractions — ``O(d·(a+b)) = O(d^1.5)`` flops
per vector instead of the dense ``O(d^2)``, and (unlike the ``O(d log d)``
butterfly, which is a chain of memory-bound shuffles) it maps onto the MXU /
BLAS.  ``fwht`` is the shared jnp implementation used by the XLA path and by
the Pallas kernel bodies below.

The fused Pallas kernels mirror ``kernels/fourier_sketch.py``: a grid over
(frequency blocks, batch tiles) where each tile's projection — here the
diag/WHT chain instead of an MXU matmul against a dense ``w`` tile — stays in
VMEM through the trig and the weighted batch reduction, so the ``(N, m)``
projection never touches HBM.  Inside the kernels each WHT stage is one
``(rows, d) @ H_d`` MXU matmul: the Kronecker form needs a ``(rows, d) ->
(rows·a, b)`` reshape that splits the lane dimension, which Mosaic cannot
lower.  ``quantized_structured_sketch_kernel`` is the
QCKM twin (dithered phases -> int32 code sums).  Off-TPU both run in
``interpret=True`` mode (callers in ``kernels/ops.py`` handle dispatch and
padding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import quantize as qz


@functools.lru_cache(maxsize=None)
def _hadamard_np(k: int) -> np.ndarray:
    """Sylvester Hadamard matrix H_k (entries ±1), k a power of two."""
    assert k >= 1 and (k & (k - 1)) == 0, k
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < k:
        h = np.block([[h, h], [h, -h]])
    return h


def kron_factors(d: int) -> tuple[int, int]:
    """Balanced Kronecker split ``d = a * b`` with ``a, b`` powers of two."""
    assert d >= 1 and (d & (d - 1)) == 0, d
    p = d.bit_length() - 1
    a = 1 << ((p + 1) // 2)
    return a, d // a


def hadamard(k: int, dtype=jnp.float32) -> jax.Array:
    """H_k as a jnp array (for the Kronecker-factored transform)."""
    return jnp.asarray(_hadamard_np(k), dtype)


def _kron_wht_2d(v: jax.Array, ha: jax.Array, hb: jax.Array) -> jax.Array:
    """(rows, d) -> (H_a ⊗ H_b) applied to each row (d = a·b)."""
    rows = v.shape[0]
    a, b = ha.shape[0], hb.shape[0]
    hi = jax.lax.Precision.HIGHEST  # f32-exact on the TPU's MXU as well
    y = jnp.dot(
        v.reshape(rows * a, b), hb, precision=hi, preferred_element_type=v.dtype
    )
    y = jnp.einsum("ij,rjk->rik", ha, y.reshape(rows, a, b), precision=hi)
    return y.reshape(rows, a * b)


def fwht(v: jax.Array) -> jax.Array:
    """Unnormalised Walsh–Hadamard transform along the last axis.

    ``v: (..., d)`` with ``d`` a power of two; returns ``v @ H_d`` (``H_d``
    symmetric, so left- and right-application coincide).  Two Kronecker
    contractions — the XLA reference path of the structured operator.
    """
    d = v.shape[-1]
    if d == 1:
        return v
    a, b = kron_factors(d)
    ha = hadamard(a, v.dtype)
    hb = hadamard(b, v.dtype)
    return _kron_wht_2d(v.reshape(-1, d), ha, hb).reshape(v.shape)


def hd_chain(xp: jax.Array, diags: jax.Array) -> jax.Array:
    """The three-stage normalised HD chain of one (or many) blocks.

    ``xp: (..., d)`` zero-padded inputs, ``diags: (..., 3, d)`` Rademacher
    signs (leading axes broadcast, e.g. ``(nblocks, 3, d)`` against
    ``(N, 1, d)``).  Returns ``c·H D_2 (c·H D_1 (c·H D_0 xp))`` with
    ``c = d^{-1/2}`` — unit-norm rows, the direction half of the operator.
    """
    d = xp.shape[-1]
    c = jnp.asarray(d, xp.dtype) ** -0.5
    v = xp
    for s in range(3):
        v = fwht(v * diags[..., s, :]) * c
    return v


# ---------------------------------------------------------------------------
# Fused Pallas kernels
# ---------------------------------------------------------------------------


def _hd_chain_tile(v, dg, h, d):
    """In-VMEM HD chain for one (rows, d) tile; dg: (1, 3, d), h: H_d."""
    c = jnp.asarray(d, v.dtype) ** -0.5
    for s in range(3):
        v = jnp.dot(
            v * dg[0, s, :][None, :], h,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ) * c
    return v


def _structured_sketch_kernel(x_ref, dg_ref, r_ref, h_ref, b_ref, cos_ref, sin_ref):
    """One (bN, d) tile: WHT-chain projection; accumulate weighted cos/sin."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cos_ref[...] = jnp.zeros_like(cos_ref)
        sin_ref[...] = jnp.zeros_like(sin_ref)

    d = x_ref.shape[-1]
    v = _hd_chain_tile(x_ref[...], dg_ref[...], h_ref[...], d)
    proj = v * r_ref[...]  # (bN, d) * (1, d) — radial rescaling
    beta = b_ref[...]  # (bN, 1)
    cos_ref[...] += jnp.sum(jnp.cos(proj) * beta, axis=0, keepdims=True)
    sin_ref[...] += jnp.sum(jnp.sin(proj) * beta, axis=0, keepdims=True)


def _quantized_structured_sketch_kernel(
    x_ref, dg_ref, r_ref, dth_ref, h_ref, v_ref, qcos_ref, qsin_ref,
    *, bits,
):
    """QCKM twin: dithered WHT-chain phases -> int32 code sums in VMEM, the
    codes ``core.quantize.quantize_codes``' (1 bit: the quadrant, no trig)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        qcos_ref[...] = jnp.zeros_like(qcos_ref)
        qsin_ref[...] = jnp.zeros_like(qsin_ref)

    d = x_ref.shape[-1]
    v = _hd_chain_tile(x_ref[...], dg_ref[...], h_ref[...], d)
    # (bN, 1) 0/1 valid mask zeroes the padding rows.
    qc, qs = qz.quantize_codes(
        v * r_ref[...], dth_ref[...], bits, valid=v_ref[...]
    )
    qcos_ref[...] += jnp.sum(qc, axis=0, keepdims=True)
    qsin_ref[...] += jnp.sum(qs, axis=0, keepdims=True)


# One frequency block's row of a per-block ``(nblocks, 1, d)`` array, seen by
# the kernel as ``(1, d)``.  The block's last two dims equal the array's, as
# the TPU lowering requires (a ``(1, d)`` block of ``(nblocks, d)`` is not
# (8, 128)-aligned).
def _freq_row(d):
    return pl.BlockSpec((None, 1, d), lambda i, j: (i, 0, 0))


def _specs(d, block_n, extra_freq_rows=0):
    """Shared in_specs for (x, diags, radii[, dither], H_d, per-row)."""
    specs = [
        pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        pl.BlockSpec((1, 3, d), lambda i, j: (i, 0, 0)),
        _freq_row(d),
    ]
    specs += [_freq_row(d)] * extra_freq_rows
    specs += [
        pl.BlockSpec((d, d), lambda i, j: (0, 0)),
        pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
    ]
    return specs


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def structured_sketch_kernel(
    x: jax.Array,  # (N, d) f32, zero-padded in both axes
    diags: jax.Array,  # (nblocks, 3, d)
    radii: jax.Array,  # (nblocks, d)
    beta: jax.Array,  # (N, 1)
    block_n: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw fused launch: inputs must be pre-padded/aligned (see ops.py).

    -> ``(cos_sums, sin_sums)`` of shape ``(nblocks, d)`` (flatten + slice to
    ``m`` in the caller).  The frequency-block width is ``d`` — the WHT needs
    the whole block resident, so there is no ``block_m`` knob here.
    """
    n_pts, d = x.shape
    nblocks = diags.shape[0]
    assert n_pts % block_n == 0, (n_pts, block_n)
    grid = (nblocks, n_pts // block_n)
    cos_s, sin_s = pl.pallas_call(
        _structured_sketch_kernel,
        grid=grid,
        in_specs=_specs(d, block_n),
        out_specs=[_freq_row(d), _freq_row(d)],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, 1, d), jnp.float32),
        ],
        interpret=interpret,
    )(x, diags, radii.reshape(nblocks, 1, d), hadamard(d), beta)
    return cos_s.reshape(nblocks, d), sin_s.reshape(nblocks, d)


@functools.partial(jax.jit, static_argnames=("bits", "block_n", "interpret"))
def quantized_structured_sketch_kernel(
    x: jax.Array,  # (N, d)
    diags: jax.Array,  # (nblocks, 3, d)
    radii: jax.Array,  # (nblocks, d)
    dither: jax.Array,  # (nblocks, d)
    valid: jax.Array,  # (N, 1)
    bits: int = 1,
    block_n: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw fused QCKM launch -> int32 ``(qcos, qsin)`` of shape (nblocks, d)."""
    n_pts, d = x.shape
    nblocks = diags.shape[0]
    assert n_pts % block_n == 0, (n_pts, block_n)
    grid = (nblocks, n_pts // block_n)
    rows = lambda v: v.reshape(nblocks, 1, d)  # noqa: E731
    qcos, qsin = pl.pallas_call(
        functools.partial(_quantized_structured_sketch_kernel, bits=bits),
        grid=grid,
        in_specs=_specs(d, block_n, extra_freq_rows=1),
        out_specs=[_freq_row(d), _freq_row(d)],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, 1, d), jnp.int32),
            jax.ShapeDtypeStruct((nblocks, 1, d), jnp.int32),
        ],
        interpret=interpret,
    )(x, diags, rows(radii), rows(dither), hadamard(d), valid)
    return qcos.reshape(nblocks, d), qsin.reshape(nblocks, d)
