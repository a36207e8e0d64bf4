"""The plain reference the benchmark holds the program to.

Written from the paper's definitions, importing nothing of the program and
taking no table from it.  Its inputs are the data the benchmark drew from
its seed and each fit's PRNG key; from them it derives, on its own, what a
fit has to produce:

- the keys of the sketch pass: ``split(fit_key)[0]`` splits three ways into
  the sigma^2 key, the frequency key and the dither key;
- the frequency scale sigma^2 (paper step 1, the small-sketch regression of
  Keriven et al., arXiv:1606.02838 §5.2): from the first ``sample`` points,
  ``iterations`` rounds of ``m0`` adapted-radius frequencies at the current
  scale, fitting ``log|z(w)| = -s·||w||^2 / 2`` over ``candidates`` scales
  spread over ``span_decades`` around it, on the points where ``|z| > trust``
  with ``|z|`` floored at ``floor``;
- the frequencies (paper step 2): ``W = R·phi``, ``phi`` uniform on the
  sphere (a normal draw, normalised), ``R`` of the adapted-radius density
  ``p(R) ∝ sqrt(R²σ² + R⁴σ⁴/4)·exp(-R²σ²/2)`` by its inverse CDF, tabulated
  on ``grid`` points over ``[0, radius_max_sigma / σ]``;
- the sketch (paper eq. (2)): ``z = [mean cos(x·W), -mean sin(x·W)]`` with
  the box bounds ``min x``, ``max x``;
- the K-means objective (eq. (1)): ``SSE = sum_i min_k ||x_i - c_k||^2``.

Random words come from ``jax.random``; the tables, the regression and the
frequencies are float64 numpy on the host; the sketch is float32
``jax.numpy`` at ``"highest"`` (six bf16 passes on the TPU).  The control is
the same sketch at ``"bf16"``, the next precision below the configuration's
float32: one bf16 pass with float32 accumulation, which is what a TPU
contraction at default precision does.  It is written out on bf16-rounded
operands, so it reads the same on the TPU and on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def sketch_keys(fit_key):
    """``(sigma2_key, frequency_key)`` of the sketch pass under ``fit_key``."""
    k_sketch = jax.random.split(fit_key)[0]
    k_sig, k_freq, _ = jax.random.split(k_sketch, 3)
    return k_sig, k_freq


def _radius_table(sigma2: float, law: dict):
    """The adapted-radius law's CDF tabulated over ``[0, radius_max_sigma/σ]``."""
    r = np.linspace(0.0, law["radius_max_sigma"] / np.sqrt(sigma2), law["grid"])
    t2 = r * r * sigma2
    cdf = np.cumsum(np.sqrt(t2 + t2 * t2 / 4.0) * np.exp(-t2 / 2.0))
    return r, cdf / cdf[-1]


def _draw(key, m: int, n: int, sigma2: float, law: dict):
    """``(u, phi, W)``: the uniforms and ``(m, n)`` unit directions drawn
    under ``key``, and the ``(n, m)`` float64 frequencies they make."""
    k_radius, k_direction = jax.random.split(key)
    u = np.asarray(jax.random.uniform(k_radius, (m,)), np.float64)
    v = np.asarray(jax.random.normal(k_direction, (m, n)), np.float64)
    phi = v / np.linalg.norm(v, axis=1, keepdims=True)
    r, cdf = _radius_table(sigma2, law)
    return u, phi, (phi * np.interp(u, cdf, r)[:, None]).T


def draw_frequencies(key, m: int, n: int, sigma2: float, law: dict) -> np.ndarray:
    """``(n, m)`` float64 frequencies of the adapted-radius law at sigma^2."""
    return _draw(key, m, n, sigma2, law)[2]


def frequency_errors(w, key, sigma2: float, law: dict):
    """``(cdf_gap, direction_gap, W)``: how far the frequencies ``w (n, m)``
    lie from the reference's draw ``W`` under ``key`` at ``sigma2``.  A radius
    is judged where the law's CDF puts it, against the uniform it has to come
    from (so the law's far tail, where a float32 table is flat, counts no
    more than its mass); a direction by its distance from the reference's."""
    w = np.asarray(w, np.float64)
    u, phi, w_ref = _draw(key, w.shape[1], w.shape[0], sigma2, law)
    radius = np.linalg.norm(w, axis=0)
    r, cdf = _radius_table(sigma2, law)
    cdf_gap = np.max(np.abs(np.interp(radius, r, cdf) - u))
    direction_gap = np.max(np.linalg.norm(
        w / np.maximum(radius, 1e-30) - phi.T, axis=0))
    return float(cdf_gap), float(direction_gap), w_ref


def estimate_sigma2(key, x, law: dict, est: dict) -> float:
    """The frequency scale of the points ``x`` (their first ``est["sample"]``)."""
    x = np.asarray(x[: est["sample"]], np.float64)
    n = x.shape[1]
    sigma2 = max(float(np.mean(np.sum((x - x.mean(axis=0)) ** 2, axis=1))) / n,
                 1e-12)
    lo, hi = est["span_decades"]
    for _ in range(est["iterations"]):
        key, k_round = jax.random.split(key)
        w = draw_frequencies(k_round, est["m0"], n, sigma2, law)
        mod = np.abs(np.mean(np.exp(1j * (x @ w)), axis=0))
        r2 = np.sum(w * w, axis=0)
        trust = (mod > est["trust"]).astype(np.float64)
        logmod = np.log(np.maximum(mod, est["floor"]))
        cands = sigma2 * np.logspace(lo, hi, est["candidates"])
        loss = [np.sum(trust * (logmod + s * r2 / 2.0) ** 2) / max(trust.sum(), 1.0)
                for s in cands]
        sigma2 = float(cands[int(np.argmin(loss))])
    return sigma2


def _bf16(a):
    """``a`` rounded to bfloat16, kept in float32.  ``reduce_precision``, not
    a round trip through ``astype``, which XLA may drop as excess precision."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


PRECISIONS = ("highest", "bf16")


def contract(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` at ``"highest"``, or as one bf16 pass
    (``"bf16"``): bf16-rounded operands, multiplied and summed in float32."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    if precision == "bf16":
        a, b = _bf16(a), _bf16(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("precision",))
def _chunk_sums(x, w, precision):
    phase = contract("bn,nm->bm", x, w, precision)
    return jnp.sum(jnp.cos(phase), axis=0), jnp.sum(jnp.sin(phase), axis=0)


def sketch(chunks, w, precision: str = "highest"):
    """Stacked-real sketch ``(2m,)`` and box bounds of the points in
    ``chunks`` (a list of ``(B, n)`` device arrays) at frequencies ``w``."""
    w = jnp.asarray(w, jnp.float32)
    cos_acc = sin_acc = 0.0
    count = 0
    for x in chunks:
        c, s = _chunk_sums(x, w, precision)
        cos_acc, sin_acc = cos_acc + c, sin_acc + s
        count += x.shape[0]
    z = jnp.concatenate([cos_acc, -sin_acc]) / count
    lower = jnp.min(jnp.stack([jnp.min(x, axis=0) for x in chunks]), axis=0)
    upper = jnp.max(jnp.stack([jnp.max(x, axis=0) for x in chunks]), axis=0)
    return z, lower, upper


@jax.jit
def _chunk_sse(x, c):
    d2 = jnp.sum((x[:, None, :] - c[None, :, :]) ** 2, axis=-1)
    return jnp.sum(jnp.min(d2, axis=1))


def sse(chunks, centroids) -> float:
    """K-means objective of ``centroids`` over every point, in float64 on
    the host over per-chunk float32 sums."""
    c = jnp.asarray(centroids, jnp.float32)
    return float(sum(float(_chunk_sse(x, c)) for x in chunks))


def rel_err(a, b) -> float:
    """``||a - b|| / ||b||`` in float64 (inf when ``a`` is not finite)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))
