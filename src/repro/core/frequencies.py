"""Frequency distributions for the sketching operator (paper §3.1).

Frequencies are drawn i.i.d. from a distribution ``Lambda``.  The paper uses the
*Adapted radius* distribution of Keriven et al. (arXiv:1606.02838): a frequency is
``omega = R * phi`` with ``phi`` uniform on the unit sphere and the radius ``R``
drawn from

    p_AR(R)  ∝  sqrt(R^2 sigma^2 + R^4 sigma^4 / 4) * exp(-R^2 sigma^2 / 2)

parametrised by a single scale ``sigma^2``.  A plain Gaussian distribution
``omega ~ N(0, I/sigma^2)`` and a folded-Gaussian radius are provided for
comparison (they appear as baselines in [5]).

``sigma^2`` is chosen by the small-sketch regression heuristic of [5]: sketch a
small fraction of the data at a few candidate scales and fit the decay of the
modulus of the empirical characteristic function.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

FreqDist = Literal["adapted_radius", "gaussian", "folded_gaussian"]

# Number of grid points for inverse-CDF sampling of the radius density.
_GRID = 4096
# The adapted-radius density has negligible mass beyond R*sigma ~ 6.
_RMAX_SIGMA = 6.0


def _adapted_radius_pdf(r: jax.Array, sigma2: jax.Array) -> jax.Array:
    """Unnormalised adapted-radius pdf evaluated at radii ``r`` (sigma = 1 units)."""
    r2 = r * r * sigma2
    return jnp.sqrt(r2 + r2 * r2 / 4.0) * jnp.exp(-r2 / 2.0)


def radius_from_uniform(u: jax.Array, sigma2: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Map uniforms ``u in [0, 1)`` through the adapted-radius inverse CDF.

    The deterministic half of the sampler (grid CDF + linear interpolation),
    split out so the f32/f64 numerics of the grid accumulation can be compared
    on identical uniforms (``dtype`` controls the grid/CDF precision).
    """
    u = jnp.asarray(u, dtype)
    # A uniform of exactly 0 (a float32 draw's 2^-23 step, ~1e-4 per
    # 1000-frequency draw) would map to the radius 0: a frequency with no
    # direction, whose sketch entry is 1 for every point.  The law has no
    # mass there, so it takes the middle of its bin instead.
    u = jnp.where(u > 0, u, jnp.asarray(2.0**-24, dtype))
    sigma2 = jnp.asarray(sigma2, dtype)
    sigma = jnp.sqrt(sigma2)
    grid = jnp.linspace(
        jnp.asarray(0.0, dtype), _RMAX_SIGMA / jnp.maximum(sigma, 1e-20), _GRID
    )
    pdf = _adapted_radius_pdf(grid, sigma2)
    cdf = jnp.cumsum(pdf)
    cdf = cdf / cdf[-1]
    idx = jnp.searchsorted(cdf, u)
    idx = jnp.clip(idx, 1, _GRID - 1)
    # Linear interpolation between grid points for a smooth sample.
    c0, c1 = cdf[idx - 1], cdf[idx]
    w = (u - c0) / jnp.maximum(c1 - c0, 1e-20)
    return grid[idx - 1] + w * (grid[idx] - grid[idx - 1])


def _inverse_cdf_sample(
    key: jax.Array, m: int, sigma2: jax.Array, dtype=jnp.float32
) -> jax.Array:
    """Draw ``m`` radii from the adapted-radius density by inverse-CDF on a grid."""
    return radius_from_uniform(jax.random.uniform(key, (m,)), sigma2, dtype)


def _uniform_sphere(key: jax.Array, m: int, n: int, dtype=jnp.float32) -> jax.Array:
    v = jax.random.normal(key, (m, n), dtype)
    return v / jnp.linalg.norm(v, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("m", "n", "dist", "dtype"))
def draw_radii(
    key: jax.Array,
    m: int,
    n: int,
    sigma2: jax.Array | float,
    dist: FreqDist = "adapted_radius",
    dtype=jnp.float32,
) -> jax.Array:
    """Draw ``m`` frequency *radii* ``||omega||`` from ``Lambda``'s radial law.

    Used by structured frequency operators (``core.freq_ops``), which pick
    directions by fast orthogonal transforms and only need the radial part of
    the distribution: adapted-radius (inverse CDF), the chi law of an
    isotropic Gaussian, or the folded Gaussian.
    """
    sigma2 = jnp.asarray(sigma2, dtype)
    if dist == "adapted_radius":
        return _inverse_cdf_sample(key, m, sigma2, dtype)
    if dist == "gaussian":
        # ||N(0, I_n / sigma2)||: chi_n scaled by 1/sigma.
        v = jax.random.normal(key, (m, n), dtype)
        return jnp.linalg.norm(v, axis=1) / jnp.sqrt(sigma2)
    if dist == "folded_gaussian":
        return jnp.abs(jax.random.normal(key, (m,), dtype)) / jnp.sqrt(sigma2)
    raise ValueError(f"unknown frequency distribution {dist!r}")


@functools.partial(jax.jit, static_argnames=("m", "n", "dist", "dtype"))
def draw_frequencies(
    key: jax.Array,
    m: int,
    n: int,
    sigma2: jax.Array | float,
    dist: FreqDist = "adapted_radius",
    dtype=jnp.float32,
) -> jax.Array:
    """Draw ``m`` frequency vectors in R^n from ``Lambda``.

    Returns ``W`` with shape ``(n, m)`` (column frequencies), so that the sketch
    inner products are ``X @ W`` for row-major data ``X: (N, n)``.  ``dtype``
    selects the sampling/output precision (default f32; propagated from
    ``CKMConfig.freq_dtype`` by the pipeline — f64 needs ``jax.enable_x64``).
    """
    kr, kd = jax.random.split(key)
    sigma2 = jnp.asarray(sigma2, dtype)
    if dist == "adapted_radius":
        radius = _inverse_cdf_sample(kr, m, sigma2, dtype)
        phi = _uniform_sphere(kd, m, n, dtype)
        w = phi * radius[:, None]
    elif dist == "gaussian":
        w = jax.random.normal(kr, (m, n), dtype) / jnp.sqrt(sigma2)
    elif dist == "folded_gaussian":
        radius = jnp.abs(jax.random.normal(kr, (m,), dtype)) / jnp.sqrt(sigma2)
        phi = _uniform_sphere(kd, m, n, dtype)
        w = phi * radius[:, None]
    else:  # pragma: no cover - static arg
        raise ValueError(f"unknown frequency distribution {dist!r}")
    return w.T.astype(dtype)  # (n, m)


# ---------------------------------------------------------------------------
# Scale (sigma^2) estimation — small-sketch regression of [5], §5.2.
# ---------------------------------------------------------------------------


def estimate_sigma2(
    key: jax.Array,
    x_sample: jax.Array,
    m0: int = 500,
    n_iters: int = 3,
    sigma2_init: float | None = None,
    n_candidates: int = 64,
) -> jax.Array:
    """Estimate the frequency-scale ``sigma^2`` from a small data fraction.

    Implements the iterative small-sketch regression heuristic of [5]: at the
    current scale, draw ``m0`` frequencies, sketch the (small) sample, and fit
    the modulus of the empirical characteristic function with the Gaussian decay
    ``|z(omega)| ≈ exp(-sigma^2 ||omega||^2 / 2)`` over a log-grid of candidate
    scales.  A couple of iterations re-centre the frequency range on the fit.

    ``x_sample`` is a *small* subset (or online head) of the dataset; a few
    thousand points suffice.
    """
    x_sample = jnp.asarray(x_sample, jnp.float32)
    n = x_sample.shape[1]
    if sigma2_init is None:
        # Coarse one-pass initial guess: mean squared distance to the sample mean
        # (an upper bound on within-cluster scale).  Stays one-pass / mergeable.
        mu = jnp.mean(x_sample, axis=0)
        sigma2 = jnp.maximum(jnp.mean(jnp.sum((x_sample - mu) ** 2, axis=1)) / n, 1e-12)
    else:
        sigma2 = jnp.asarray(sigma2_init, jnp.float32)

    for it in range(n_iters):
        key, kf = jax.random.split(key)
        w = draw_frequencies(kf, m0, n, sigma2, dist="adapted_radius")  # (n, m0)
        # Small sketch of the sample (modulus of empirical characteristic fn).
        # f32-exact on the TPU too (its default f32 matmul is one bf16 pass).
        proj = jnp.matmul(x_sample, w, precision=jax.lax.Precision.HIGHEST)
        zr = jnp.mean(jnp.cos(proj), axis=0)
        zi = jnp.mean(jnp.sin(proj), axis=0)
        mod = jnp.sqrt(zr**2 + zi**2)  # (m0,)
        r2 = jnp.sum(w * w, axis=0)  # ||omega||^2
        # Fit |z| ≈ exp(-s * r2 / 2) over candidate s on a log grid around the
        # current scale; least squares in log-modulus with a floor to avoid the
        # noise region |z| ~ 1/sqrt(S).
        cands = sigma2 * jnp.logspace(-2.0, 2.0, n_candidates)
        logmod = jnp.log(jnp.maximum(mod, 1e-3))
        weights = (mod > 0.05).astype(jnp.float32)  # trust only the low-noise region

        def loss(s):
            pred = -s * r2 / 2.0
            return jnp.sum(weights * (logmod - pred) ** 2) / jnp.maximum(
                jnp.sum(weights), 1.0
            )

        losses = jax.vmap(loss)(cands)
        sigma2 = cands[jnp.argmin(losses)]
    return jnp.asarray(sigma2, jnp.float32)
