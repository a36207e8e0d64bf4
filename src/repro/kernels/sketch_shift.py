"""Pallas TPU kernel: fused sketch-and-shift score/shift step.

The sketch-and-shift decoder (``core.decoders.sketch_shift``) ascends the
sketched density surrogate

    f(c)      = (1/m) sum_j [ cos(w_j^T c) z1_j - sin(w_j^T c) z2_j ]
    grad f(c) = (1/m) sum_j w_j [ -sin(w_j^T c) z1_j - cos(w_j^T c) z2_j ]

for a block of P candidate centroids per iteration (``z = [z1, z2]`` is the
stacked-real sketch).  The hot spot is the same shape as the sketch itself —
a ``(P, n) @ (n, m)`` projection, elementwise trig, and a reduction over m —
so it gets the same treatment: the projection tile stays in VMEM, the MXU
computes the candidate x frequency tile, the VPU applies trig and combines
with the sketch entries in place, and a second MXU pass contracts the
combined tile against ``W^T`` for the gradient.  The naive XLA path
materialises the ``(P, m)`` trig matrices in HBM each of the T mean-shift
iterations; here only candidates, frequencies, and the (P, n+1) outputs move.

Grid: ``(p_blocks, m_blocks)`` — the m (frequency) axis is the innermost grid
dimension so the ``(bP, 1)`` density and ``(bP, n)`` gradient output blocks
stay resident in VMEM while the frequencies stream through them (Pallas
revisiting semantics).

TPU alignment: callers (ops.py) pad P to the block size, m to a multiple of
the lane width (128) with zero frequency columns AND zero sketch entries
(zero-padded frequencies contribute ``cos(0)*z1_pad = 0`` to f and a zero
column to the gradient contraction), and n to a multiple of 8 with zero
features.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _shift_kernel(c_ref, w_ref, z1_ref, z2_ref, f_ref, g_ref):
    """One (bP, bM) tile: proj = c @ w; accumulate density + gradient sums."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        f_ref[...] = jnp.zeros_like(f_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    # MXU: (bP, n) @ (n, bM), f32-exact (see fourier_sketch.py).
    proj = jnp.dot(
        c_ref[...], w_ref[...], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    z1 = z1_ref[...]  # (1, bM)
    z2 = z2_ref[...]
    cosp = jnp.cos(proj)
    sinp = jnp.sin(proj)
    # VPU: combine trig with the sketch entries, reduce over the m tile.
    f_ref[...] += jnp.sum(cosp * z1 - sinp * z2, axis=1, keepdims=True)
    # MXU: gradient contraction of the combined tile against W^T.
    t = -sinp * z1 - cosp * z2  # (bP, bM)
    g_ref[...] += jnp.dot(
        t, w_ref[...].T, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(
    jax.jit, static_argnames=("block_p", "block_m", "interpret")
)
def sketch_shift_kernel(
    c: jax.Array,
    w: jax.Array,
    z1: jax.Array,
    z2: jax.Array,
    block_p: int = 256,
    block_m: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel launch: inputs must be pre-padded/aligned (see ops.py).

    c: (P, n) f32, w: (n, m) f32, z1/z2: (1, m) f32
    -> (density sums (P, 1), gradient sums (P, n)) f32 — unnormalised (no 1/m).
    """
    p_cand, feat = c.shape
    m = w.shape[1]
    assert p_cand % block_p == 0 and m % block_m == 0, (p_cand, m)
    grid = (p_cand // block_p, m // block_m)
    return pl.pallas_call(
        _shift_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_p, feat), lambda i, j: (i, 0)),
            pl.BlockSpec((feat, block_m), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_p, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_p, feat), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p_cand, 1), jnp.float32),
            jax.ShapeDtypeStruct((p_cand, feat), jnp.float32),
        ],
        interpret=interpret,
    )(c, w, z1, z2)
