"""The plain reference of the 1-bit quantized sketch (QCKM).

Written from the definitions, importing nothing of the program and taking
no table from it.  sigma^2, the frequencies and the K-means objective are
``chipbench.reference``'s; this module adds what quantization changes:

- the dither: ``xi ~ U[0, 2·pi)^m`` drawn as float32 by ``jax.random.uniform``
  under the third branch of ``split(split(fit_key)[0], 3)`` (the first two
  are the sigma^2 and frequency keys of ``reference.sketch_keys``);
- the codes of a point ``x`` (Schellekens & Jacques, arXiv:1804.10109, the
  universal 1-bit quantizer of the dithered phase): ``theta = x·W + xi``,
  ``q_c = sign(cos theta)``, ``q_s = sign(sin theta)``, with 0 mapped to +1,
  summed over every point as integers;
- the dequantization: the square wave ``sign(cos t)`` has the Fourier series
  ``(4/pi)·(cos t - cos 3t / 3 + ...)``, so ``pi/4`` times the code sums
  estimates the sums of ``cos(theta)`` and ``sin(theta)``; a rotation by
  ``-xi`` turns those into sums of ``cos(x·W)`` and ``sin(x·W)``, and the
  sketch is ``[sum cos, -sum sin] / N``, as the float sketch (paper eq. 2).

The phases are float32 ``jax.numpy`` at ``"highest"``; the code sums are
int32 on the device, the dequantization float64 numpy on the host.  The
control is the same codes from one-pass bf16 phases
(``reference.contract(..., "bf16")``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as ref


def dither(fit_key, m: int):
    """The ``(m,)`` float32 dither of the fit under ``fit_key``."""
    k_dither = jax.random.split(jax.random.split(fit_key)[0], 3)[2]
    return jax.random.uniform(k_dither, (m,), jnp.float32, 0.0, 2.0 * math.pi)


@functools.partial(jax.jit, static_argnames=("precision",))
def _chunk_codes(x, w, xi, precision):
    theta = ref.contract("bn,nm->bm", x, w, precision) + xi
    q_c = jnp.where(jnp.cos(theta) >= 0, 1, -1).astype(jnp.int32)
    q_s = jnp.where(jnp.sin(theta) >= 0, 1, -1).astype(jnp.int32)
    return jnp.sum(q_c, axis=0), jnp.sum(q_s, axis=0)


def code_sums(chunks, w, xi, precision: str = "highest"):
    """int64 ``(sum q_c, sum q_s)``, each ``(m,)``, over every point of
    ``chunks`` at frequencies ``w (n, m)`` and dither ``xi (m,)``."""
    w = jnp.asarray(w, jnp.float32)
    xi = jnp.asarray(xi, jnp.float32)
    q_c = q_s = 0
    for x in chunks:
        c, s = _chunk_codes(x, w, xi, precision)
        q_c = q_c + np.asarray(c, np.int64)
        q_s = q_s + np.asarray(s, np.int64)
    return q_c, q_s


def dequantize(q_c, q_s, xi, count: int) -> np.ndarray:
    """Stacked-real ``(2m,)`` sketch from the code sums of ``count`` points."""
    xi = np.asarray(xi, np.float64)
    s_c = (math.pi / 4.0) * np.asarray(q_c, np.float64)  # ~ sum cos(x·W + xi)
    s_s = (math.pi / 4.0) * np.asarray(q_s, np.float64)  # ~ sum sin(x·W + xi)
    cos_sum = np.cos(xi) * s_c + np.sin(xi) * s_s  # cos(t) = cos(t+xi)cos(xi) + sin(t+xi)sin(xi)
    sin_sum = np.cos(xi) * s_s - np.sin(xi) * s_c  # sin(t) = sin(t+xi)cos(xi) - cos(t+xi)sin(xi)
    return np.concatenate([cos_sum, -sin_sum]) / count


def sketch(chunks, w, xi, precision: str = "highest"):
    """Dequantized ``(2m,)`` sketch and box bounds of the points in
    ``chunks`` (a list of ``(B, n)`` device arrays)."""
    q_c, q_s = code_sums(chunks, w, xi, precision)
    count = sum(int(x.shape[0]) for x in chunks)
    lower = jnp.min(jnp.stack([jnp.min(x, axis=0) for x in chunks]), axis=0)
    upper = jnp.max(jnp.stack([jnp.max(x, axis=0) for x in chunks]), axis=0)
    return dequantize(q_c, q_s, xi, count), lower, upper
