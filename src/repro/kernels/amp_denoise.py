"""Pallas TPU kernel: fused truncated-Gaussian posterior denoiser (CL-AMP).

The input channel of the CL-AMP decoder (``core.decoders.amp``) updates all K
centroid estimates at once: each pseudo-data entry ``r_kl`` with pseudo
-variance ``q`` is combined with the uniform box prior ``[lower_l, upper_l]``,
giving the truncated-normal posterior whose mean/variance drive the next GAMP
iteration.  The whole update is elementwise over the ``(K, n)`` estimate
matrix, so one VPU pass computes both moments in place — the unfused XLA path
materialises the five intermediate ``(K, n)`` arrays (a, b, Z, and the two
pdf terms) in HBM between elementwise ops; here only ``r`` and the two output
moments move.

Numerics (one body, :func:`truncated_normal_moments`, shared with
``ops.amp_denoise``'s XLA path and mirrored by the
``kernels.ref.amp_denoise_ref`` oracle):

    a = (lo - r)/sig,  b = (hi - r)/sig,       sig = sqrt(q)
    Z = Phi(b) - Phi(a)                        (via erfc, tail-stable)
    mean = r + sig (phi(a) - phi(b)) / Z
    var  = q [1 + (a phi(a) - b phi(b))/Z - ((phi(a) - phi(b))/Z)^2]

Mosaic has no lowering for the ``erfc`` primitive, so inside the kernel it
is written out in ``exp`` and polynomials (:func:`erfc`, the f32
approximation XLA itself expands ``lax.erfc`` into); the XLA path keeps
``lax.erfc``.  The two agree within a few f32 ulps.  Both carry the hardened
edge cases: infinite box edges contribute zero boundary
terms (``a * phi(a)`` would be ``inf * 0``), and ``Z < 1e-12`` (pseudo-data
far outside the box — the regime a diverging AMP iterate visits) collapses
the posterior to the nearest box edge with a small residual variance instead
of 0/0 NaNs.

Grid: ``(k_blocks,)`` over rows of the estimate matrix; every block is
``(block_k, n)`` with the bounds/variance broadcast as ``(1, n)`` rows.  TPU
alignment: callers (ops.py) pad K to the block size and n to the lane width
(128) with benign values (r=0, lo=-1, hi=1, q=1); padded cells are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INV_SQRT2 = 0.7071067811865476
_INV_SQRT2PI = 0.3989422804014327
# f32 polynomial coefficients of erf on |x| < 1 (x * T(x^2)) and of erfc on
# |x| >= 1 (exp(-x^2) / |x| * P or R of 1/x^2, P below 2 and R from 2 up).
_ERF_T = (
    7.853861353153693e-5, -8.010193625184903e-4, 5.188327685732524e-3,
    -2.685381193529856e-2, 1.128358514861418e-1, -3.761262582423300e-1,
    1.128379165726710e0,
)
_ERFC_P = (
    2.326819970068386e-2, -1.387039388740657e-1, 3.687424674597105e-1,
    -5.824733027278666e-1, 6.210004621745983e-1, -4.944515323274145e-1,
    3.404879937665872e-1, -2.741127028184656e-1, 5.638259427386472e-1,
)
_ERFC_R = (
    -1.047766399936249e1, 1.297719955372516e1, -7.495518717768503e0,
    2.921019019210786e0, -1.015265279202700e0, 4.218463358204948e-1,
    -2.820767439740514e-1, 5.641895067754075e-1,
)
_MAXLOG = 88.72283905206835  # exp(-x^2) underflows f32 beyond this


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def erfc(x):
    """f32 complementary error function from ``exp`` and polynomials.

    The same approximation XLA expands ``lax.erfc`` into (relative error a
    few f32 ulps, exact 0 and 2 at ``±inf``), so it lowers inside a Pallas
    TPU kernel and agrees with ``lax.erfc`` elsewhere.
    """
    ax = jnp.abs(x)
    x2 = x * x
    # |x| < 1: 1 - erf(x).
    small = 1.0 - x * _horner(x2, _ERF_T)
    # |x| >= 1: exp(-x^2)/|x| * poly(1/x^2); 0 once exp(-x^2) underflows.
    inv = 1.0 / ax
    inv2 = inv * inv
    poly = jnp.where(ax < 2.0, _horner(inv2, _ERFC_P), _horner(inv2, _ERFC_R))
    big = jnp.where(-x2 < -_MAXLOG, 0.0, jnp.exp(-x2) * inv * poly)
    big = jnp.where(x < 0, 2.0 - big, big)
    return jnp.where(ax < 1.0, small, big)


def truncated_normal_moments(r, q, lo, hi, erfc_fn=erfc):
    """Posterior ``(mean, var)`` of ``N(r, q)`` truncated to ``[lo, hi]``.

    Elementwise with broadcasting; ``q`` already clamped positive.  The body
    of the Pallas kernel (``erfc_fn`` = :func:`erfc`) and of the
    ``impl="xla"`` path of ``ops.amp_denoise`` (``erfc_fn=lax.erfc``).
    """
    sig = jnp.sqrt(q)
    a = (lo - r) / sig
    b = (hi - r) / sig
    pa = _INV_SQRT2PI * jnp.exp(-0.5 * a * a)
    pb = _INV_SQRT2PI * jnp.exp(-0.5 * b * b)
    # Phi(b) - Phi(a), tail-stable: erfc keeps relative precision deep in
    # either tail where erf rounds to +-1 (Phi(b) - Phi(a) == Phi(-a) -
    # Phi(-b); the where picks the branch whose erfc arguments are positive).
    z_mass = 0.5 * jnp.where(
        a + b > 0,
        erfc_fn(a * _INV_SQRT2) - erfc_fn(b * _INV_SQRT2),
        erfc_fn(-b * _INV_SQRT2) - erfc_fn(-a * _INV_SQRT2),
    )
    z_mass = jnp.maximum(z_mass, 1e-30)
    inside = z_mass > 1e-12
    # Infinite box edges: the boundary terms t*phi(t) vanish (inf * 0 guard).
    apa = jnp.where(jnp.isfinite(a), a * pa, 0.0)
    bpb = jnp.where(jnp.isfinite(b), b * pb, 0.0)
    frac = (pa - pb) / z_mass
    mean = r + sig * frac
    var = q * (1.0 + (apa - bpb) / z_mass - frac * frac)
    mean = jnp.where(inside, mean, jnp.clip(r, lo, hi))
    var = jnp.where(inside, var, q * 1e-6)
    return jnp.clip(mean, lo, hi), jnp.clip(var, q * 1e-12, q)


def _denoise_kernel(r_ref, q_ref, lo_ref, hi_ref, mean_ref, var_ref):
    """One (bK, n) tile: both truncated-normal moments in a single VPU pass."""
    mean_ref[...], var_ref[...] = truncated_normal_moments(
        r_ref[...], q_ref[...], lo_ref[...], hi_ref[...]
    )


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def amp_denoise_kernel(
    r: jax.Array,
    q: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    block_k: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel launch: inputs must be pre-padded/aligned (see ops.py).

    r: (K, n) f32; q/lo/hi: (1, n) f32 -> (mean (K, n), var (K, n)) f32.
    """
    k_est, feat = r.shape
    assert k_est % block_k == 0, (k_est, block_k)
    grid = (k_est // block_k,)
    return pl.pallas_call(
        _denoise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_k, feat), lambda i: (i, 0)),
            pl.BlockSpec((1, feat), lambda i: (0, 0)),
            pl.BlockSpec((1, feat), lambda i: (0, 0)),
            pl.BlockSpec((1, feat), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_k, feat), lambda i: (i, 0)),
            pl.BlockSpec((block_k, feat), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_est, feat), jnp.float32),
            jax.ShapeDtypeStruct((k_est, feat), jnp.float32),
        ],
        interpret=interpret,
    )(r, q, lo, hi)
