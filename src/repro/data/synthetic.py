"""Synthetic datasets used by the paper's experiments (§4.1).

- ``gaussian_mixture``: K unit Gaussians in R^n with uniform weights, means
  drawn N(0, c K^{1/n} Id), c = 1.5 ("so that clusters are sufficiently
  separated with high probability").
- ``sbm_spectral``: offline stand-in for the paper's MNIST spectral-clustering
  pipeline (SIFT + kNN graph + Laplacian eigenvectors are not reproducible in
  this container): a stochastic block model graph whose normalised-Laplacian
  eigenvectors give the same kind of 10-dimensional spectral features the
  paper clusters.  Protocol (embed -> K-means -> ARI) is unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def gaussian_mixture(
    key: jax.Array,
    n_points: int,
    k: int,
    n: int,
    c: float = 1.5,
    return_labels: bool = False,
    means: jax.Array | None = None,
):
    """Draw ``n_points`` from the paper's mixture of K unit Gaussians in R^n.

    ``means`` (``(k, n)``) fixes the mixture instead of drawing it from
    ``key``, so a stream of chunks, each drawn under its own key, samples one
    distribution (``key`` then only drives labels and noise).
    """
    kmu, kz, kx = jax.random.split(key, 3)
    if means is None:
        means = jax.random.normal(kmu, (k, n)) * jnp.sqrt(c * k ** (1.0 / n))
    labels = jax.random.randint(kz, (n_points,), 0, k)
    x = means[labels] + jax.random.normal(kx, (n_points, n))
    if return_labels:
        return x.astype(jnp.float32), labels, means
    return x.astype(jnp.float32)


def sbm_spectral(
    seed: int,
    n_nodes: int,
    k: int = 10,
    p_in: float = 0.08,
    p_out: float = 0.005,
    dim: int | None = None,
):
    """Spectral embedding of a stochastic block model graph.

    Returns ``(features (n_nodes, dim), labels (n_nodes,))`` where features are
    the first ``dim`` (default K) eigenvectors of the normalised Laplacian —
    the same 10-dim feature vectors the paper runs CKM on for MNIST.
    Dense numpy eigendecomposition: keep ``n_nodes`` at a few thousand.
    """
    rng = np.random.default_rng(seed)
    dim = dim or k
    labels = rng.integers(0, k, size=n_nodes)
    same = labels[:, None] == labels[None, :]
    probs = np.where(same, p_in, p_out)
    upper = np.triu(rng.random((n_nodes, n_nodes)) < probs, 1)
    adj = (upper | upper.T).astype(np.float64)
    deg = adj.sum(1)
    deg = np.maximum(deg, 1.0)
    d_isqrt = 1.0 / np.sqrt(deg)
    lap = np.eye(n_nodes) - d_isqrt[:, None] * adj * d_isqrt[None, :]
    vals, vecs = np.linalg.eigh(lap)
    feats = vecs[:, :dim]  # eigenvectors of the smallest eigenvalues
    # Row-normalise (standard spectral clustering post-processing).
    feats = feats / np.maximum(np.linalg.norm(feats, axis=1, keepdims=True), 1e-12)
    return feats.astype(np.float32), labels


def adjusted_rand_index(a: np.ndarray, b: np.ndarray) -> float:
    """ARI [32] between two label vectors (pure numpy)."""
    a = np.asarray(a)
    b = np.asarray(b)
    n = a.size
    ca = np.unique(a, return_inverse=True)[1]
    cb = np.unique(b, return_inverse=True)[1]
    table = np.zeros((ca.max() + 1, cb.max() + 1), np.int64)
    np.add.at(table, (ca, cb), 1)
    comb = lambda x: x * (x - 1) / 2.0
    sum_ij = comb(table).sum()
    sum_a = comb(table.sum(1)).sum()
    sum_b = comb(table.sum(0)).sum()
    expected = sum_a * sum_b / comb(n)
    max_index = 0.5 * (sum_a + sum_b)
    denom = max_index - expected
    if denom == 0:
        return 1.0
    return float((sum_ij - expected) / denom)
