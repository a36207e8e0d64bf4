"""Pallas TPU kernel: fused nearest-centroid assignment (Lloyd-Max hot loop).

Computes, for each point, ``argmin_k ||x_i - c_k||^2`` and the min distance in
one pass: the ``(bN, n)·(n, K)`` distance tile is produced on the MXU and
immediately reduced (argmin) on the VPU — the ``(N, K)`` distance matrix never
reaches HBM.  This is the assignment step of the paper's Lloyd-Max baseline;
on a v5e it turns the assignment from memory-bound (O(NK) bytes) into
compute-bound (O(N n K) flops at O(K) intensity).

The centroid set (K, n) is small and lives fully in VMEM for every tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(x_ref, c_ref, c2_ref, idx_ref, dist_ref):
    x = x_ref[...]  # (bN, n)
    c = c_ref[...]  # (K, n)
    # d2(i,k) = ||x_i||^2 - 2 x_i.c_k + ||c_k||^2 ; the x^2 term is constant
    # per-row and irrelevant to the argmin, but needed for the min distance.
    xc = jnp.dot(  # (bN, K) on the MXU, f32-exact (see fourier_sketch.py)
        x, c.T, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    d2 = c2_ref[...] - 2.0 * xc  # (bN, K)
    idx_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)
    x2 = jnp.sum(x * x, axis=1)
    dist_ref[...] = jnp.min(d2, axis=1) + x2


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def assign_argmin_kernel(
    x: jax.Array,
    c: jax.Array,
    block_n: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel launch: inputs must be pre-padded/aligned (see ops.py).

    x: (N, n) f32, c: (K, n) f32 -> (assignment (N,) i32, min_dist (N,) f32)
    """
    n_pts, feat = x.shape
    k = c.shape[0]
    assert n_pts % block_n == 0
    c2 = jnp.sum(c * c, axis=1)[None, :]  # (1, K) precomputed once
    grid = (n_pts // block_n,)
    return pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, feat), lambda i: (i, 0)),
            pl.BlockSpec((k, feat), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n,), lambda i: (i,)),
            pl.BlockSpec((block_n,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pts,), jnp.int32),
            jax.ShapeDtypeStruct((n_pts,), jnp.float32),
        ],
        interpret=interpret,
    )(x, c, c2)
