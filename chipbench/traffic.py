"""General traffic generator, parameterised by ``traffic/<name>.json``.

Data is drawn on the device from the run's seed, in bulk; the same seed
gives the same points.

- ``fit_repeat``: the paper's mixture of K unit Gaussians in R^n with
  means ~ N(0, c·K^{1/n}·I) (Keriven et al. 2017, §4.1; c = 1.5), drawn
  as device-resident chunks (a copy of ``chip_smoke.mixture_stream``,
  drawn in one jitted call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def mixture_means(key, k: int, n: int, c: float):
    return jax.random.normal(key, (k, n)) * jnp.sqrt(c * k ** (1.0 / n))


@functools.partial(jax.jit, static_argnames=("chunks", "chunk"))
def _mixture_chunks(key, means, chunks: int, chunk: int):
    k, n = means.shape

    def draw(kk):
        kz, kx = jax.random.split(kk)
        labels = jax.random.randint(kz, (chunk,), 0, k)
        return means[labels] + jax.random.normal(kx, (chunk, n))

    xs = jax.vmap(draw)(jax.random.split(key, chunks))
    return tuple(xs[i] for i in range(chunks))


def mixture_chunks(key, points: int, chunk: int, k: int, n: int, c: float):
    """``(chunks, means)``: ``points // chunk`` device arrays ``(chunk, n)``
    of one mixture, and its ``(k, n)`` means."""
    k_means, k_data = jax.random.split(key)
    means = mixture_means(k_means, k, n, c)
    return list(_mixture_chunks(k_data, means, points // chunk, chunk)), means
