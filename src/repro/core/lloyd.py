"""Lloyd-Max K-means + k-means++ — the paper's baseline, in JAX.

Matches Matlab's ``kmeans`` semantics closely enough for the paper's
comparisons: random ("range"/"sample") or k-means++ seeding, Lloyd iterations
to convergence (fixed max iteration budget + movement tolerance), empty
clusters keep their previous centroid.  Replicates are ``vmap``-ed over keys
and selected by SSE — which the baseline *can* evaluate, unlike CKM.

A ``shard_map`` distributed variant lives in ``core.distributed_sketch`` /
``data.clustering``; the assignment hot loop has a fused Pallas kernel in
``kernels/assign_argmin.py`` (used on TPU; jnp fallback here).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LloydConfig:
    k: int
    max_iters: int = 100
    tol: float = 1e-4
    init: str = "range"  # "range" | "sample" | "kpp"
    replicates: int = 1
    use_kernel: bool = False  # fused Pallas assignment (interpret mode on CPU)


class LloydResult(NamedTuple):
    centroids: jax.Array
    sse: jax.Array
    iters: jax.Array


def _init_centroids(key, x, lo, hi, cfg: LloydConfig):
    n_pts, n = x.shape
    if cfg.init == "range":
        return jax.random.uniform(key, (cfg.k, n), minval=lo, maxval=hi)
    if cfg.init == "sample":
        idx = jax.random.choice(key, n_pts, (cfg.k,), replace=False)
        return x[idx]
    # k-means++ (D^2 seeding), exactly [9].
    k0, key = jax.random.split(key)
    first = x[jax.random.randint(k0, (), 0, n_pts)]
    cents = jnp.zeros((cfg.k, n), x.dtype).at[0].set(first)
    d2 = jnp.sum((x - first) ** 2, axis=1)

    def body(i, carry):
        cents, d2, key = carry
        key, kc = jax.random.split(key)
        idx = jax.random.categorical(kc, jnp.log(jnp.maximum(d2, 1e-30)))
        c = x[idx]
        cents = cents.at[i].set(c)
        d2 = jnp.minimum(d2, jnp.sum((x - c) ** 2, axis=1))
        return cents, d2, key

    cents, _, _ = jax.lax.fori_loop(1, cfg.k, body, (cents, d2, key))
    return cents


# Exact f32 contractions on the TPU too (its default f32 matmul is one bf16
# pass, which would round the points' coordinates).
_HI = jax.lax.Precision.HIGHEST


def _assign(x, cents):
    """Nearest-centroid assignment (jnp fallback of the Pallas kernel)."""
    d2 = (
        jnp.sum(x * x, axis=1, keepdims=True)
        - 2.0 * jnp.matmul(x, cents.T, precision=_HI)
        + jnp.sum(cents * cents, axis=1)[None, :]
    )
    return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)


@functools.partial(jax.jit, static_argnames=("cfg",))
def lloyd(key: jax.Array, x: jax.Array, cfg: LloydConfig) -> LloydResult:
    """One replicate of Lloyd-Max (``kmeans`` in the paper's figures)."""
    x = jnp.asarray(x, jnp.float32)
    lo = jnp.min(x, axis=0)
    hi = jnp.max(x, axis=0)
    cents0 = _init_centroids(key, x, lo, hi, cfg)

    def cond(carry):
        _, it, moved = carry
        return jnp.logical_and(it < cfg.max_iters, moved > cfg.tol)

    if cfg.use_kernel:
        from repro.kernels import ops as kops

        assign_fn = kops.assign_argmin
    else:
        assign_fn = _assign

    def body(carry):
        cents, it, _ = carry
        assign, _ = assign_fn(x, cents)
        one_hot = jax.nn.one_hot(assign, cfg.k, dtype=x.dtype)  # (N, K)
        counts = jnp.sum(one_hot, axis=0)  # (K,)
        sums = jnp.matmul(one_hot.T, x, precision=_HI)  # (K, n)
        new = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), cents
        )
        moved = jnp.max(jnp.abs(new - cents))
        return new, it + 1, moved

    cents, iters, _ = jax.lax.while_loop(
        cond, body, (cents0, jnp.asarray(0), jnp.asarray(jnp.inf, jnp.float32))
    )
    _, mind2 = assign_fn(x, cents)
    return LloydResult(cents, jnp.sum(mind2), iters)


def kmeans(key: jax.Array, x: jax.Array, cfg: LloydConfig) -> LloydResult:
    """Lloyd-Max with replicates; the best-SSE replicate is returned."""
    if cfg.replicates == 1:
        return lloyd(key, x, cfg)
    keys = jax.random.split(key, cfg.replicates)
    res = jax.vmap(lambda k_: lloyd(k_, x, cfg))(keys)
    best = jnp.argmin(res.sse)
    return LloydResult(res.centroids[best], res.sse[best], res.iters[best])
