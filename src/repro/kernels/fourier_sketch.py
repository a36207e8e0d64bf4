"""Pallas TPU kernel: fused random-Fourier-feature sketch.

The sketch hot-spot is  z = sum_i beta_i [cos(x_i W); sin(x_i W)] — a
``(N, n) @ (n, m)`` matmul followed by elementwise trig and a reduction over N.
The naive XLA path materialises the ``(N, m)`` projection in HBM (O(N m) bytes
moved three times: write proj, read for trig, read for reduce).  This kernel
keeps each projection *tile* in VMEM: the MXU computes a ``(bN, n)·(n, bM)``
tile, the VPU applies cos/sin in place, and the weighted batch-reduction
accumulates straight into the output block across the reduction grid axis.
Arithmetic intensity goes from O(1) to O(bN) — the op flips from memory-bound
to compute-bound (see EXPERIMENTS.md §Kernels for the roofline numbers).

Grid: ``(m_blocks, n_blocks_of_N)`` — the N axis is the innermost (fastest)
grid dimension so each output block stays resident in VMEM while the batch
streams through it (Pallas revisiting semantics).

TPU alignment: callers (ops.py) pad m to a multiple of the lane width (128),
N to the block size, and the feature dim n to a multiple of 8; f32 tiles are
(8, 128)-aligned.

``quantized_fourier_sketch_kernel`` is the QCKM (core/quantize.py) variant of
the same tiling: it adds the per-frequency dither to the projection tile,
turns the phases into integer codes on the VPU, and accumulates **int32** sums
— signs never leave VMEM unaccumulated, so the quantized encoder costs the
same HBM traffic as the float one while its partial state shrinks to integer
accumulators.  Its 1-bit code needs no cos or sin: ``sign(cos)`` and
``sign(sin)`` are read from the quadrant of the phase, found by a
three-constant reduction against pi/2 (``core.quantize.one_bit_codes``, the
helper every backend shares, so their code sums agree bitwise).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import quantize as qz


def split_matmul(a: jax.Array, b: jax.Array, *, in_kernel: bool = False):
    """``a @ b`` to f32 accuracy in one single-pass bf16 matmul that a Pallas
    kernel and XLA compute bitwise alike on the TPU.

    Each operand splits exactly into three bf16 parts (hi + mid + lo); the
    six cross terms f32 resolves are concatenated along the contraction
    (each part padded to a multiple of 8, as the kernels pad features) and
    contracted in one pass with f32 accumulation.  The quantized encoder
    needs the bitwise agreement: a one-ulp phase difference flips the code
    of a point on a sign boundary, and two ``precision=HIGHEST`` matmuls,
    one per compiler, round apart.  Rounding to bf16 is ``astype`` inside a
    kernel (Mosaic has no ``reduce_precision``) and ``reduce_precision``
    outside (XLA may drop an ``astype`` round trip as excess precision).
    """
    bf16, f32 = jnp.bfloat16, jnp.float32

    def pad8(v, axis):
        widths = [(0, 0)] * v.ndim
        widths[axis] = (0, (-v.shape[axis]) % 8)
        return jnp.pad(v, widths) if widths[axis][1] else v

    if in_kernel:
        def split(v):
            hi = v.astype(bf16)
            r = v - hi.astype(f32)
            mid = r.astype(bf16)
            return hi, mid, (r - mid.astype(f32)).astype(bf16)
    else:
        def split(v):
            rp = functools.partial(
                jax.lax.reduce_precision, exponent_bits=8, mantissa_bits=7
            )
            hi = rp(v)
            mid = rp(v - hi)
            return hi, mid, rp(v - hi - mid)

    ah, am, al = split(pad8(a, a.ndim - 1))
    bh, bm, bl = split(pad8(b, 0))
    lhs = jnp.concatenate([al, ah, am, am, ah, ah], axis=-1).astype(bf16)
    rhs = jnp.concatenate([bh, bl, bm, bh, bm, bh], axis=0).astype(bf16)
    return jnp.dot(lhs, rhs, preferred_element_type=f32)


def _sketch_kernel(x_ref, w_ref, b_ref, cos_ref, sin_ref):
    """One (bN, bM) tile: proj = x @ w; accumulate beta-weighted cos/sin."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        cos_ref[...] = jnp.zeros_like(cos_ref)
        sin_ref[...] = jnp.zeros_like(sin_ref)

    # MXU: (bN, n) @ (n, bM).  HIGHEST: the TPU's default f32 matmul is one
    # bf16 pass, which puts ~0.2 rad of error on phases of tens of radians.
    proj = jnp.dot(
        x_ref[...], w_ref[...], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    beta = b_ref[...]  # (bN, 1)
    # VPU: trig + weighted reduce over the batch tile, all in VMEM.
    cos_ref[...] += jnp.sum(jnp.cos(proj) * beta, axis=0, keepdims=True)
    sin_ref[...] += jnp.sum(jnp.sin(proj) * beta, axis=0, keepdims=True)


def _quantized_sketch_kernel(x_ref, w_ref, d_ref, v_ref, qcos_ref, qsin_ref, *, bits):
    """One (bN, bM) tile of the QCKM encoder: dithered phases -> int32 codes.

    The codes are ``core.quantize.quantize_codes``' (``bits`` is static): at
    1 bit the phase's quadrant, with no cos or sin; at b bits
    ``round(S * cos/sin)``.  The whole tile stays in VMEM: MXU projection,
    VPU codes, and an integer batch-reduction straight into the int32 output
    block.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        qcos_ref[...] = jnp.zeros_like(qcos_ref)
        qsin_ref[...] = jnp.zeros_like(qsin_ref)

    # MXU: the phases bitwise as core.sketch.sketch_quantized computes them.
    proj = split_matmul(x_ref[...], w_ref[...], in_kernel=True)
    # (bN, 1) 0/1 valid mask zeroes the padding rows.
    qc, qs = qz.quantize_codes(proj, d_ref[...], bits, valid=v_ref[...])
    qcos_ref[...] += jnp.sum(qc, axis=0, keepdims=True)
    qsin_ref[...] += jnp.sum(qs, axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("bits", "block_n", "block_m", "interpret")
)
def quantized_fourier_sketch_kernel(
    x: jax.Array,
    w: jax.Array,
    dither: jax.Array,
    valid: jax.Array,
    bits: int = 1,
    block_n: int = 1024,
    block_m: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw QCKM kernel launch: inputs must be pre-padded/aligned (see ops.py).

    x: (N, n) f32, w: (n, m) f32, dither: (1, m) f32, valid: (N, 1) f32
    -> (q_cos_sums (1, m), q_sin_sums (1, m)) int32
    """
    n_pts, feat = x.shape
    m = w.shape[1]
    assert n_pts % block_n == 0 and m % block_m == 0, (n_pts, m)
    grid = (m // block_m, n_pts // block_n)
    return pl.pallas_call(
        functools.partial(_quantized_sketch_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, feat), lambda i, j: (j, 0)),
            pl.BlockSpec((feat, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.int32),
            jax.ShapeDtypeStruct((1, m), jnp.int32),
        ],
        interpret=interpret,
    )(x, w, dither, valid)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def fourier_sketch_kernel(
    x: jax.Array,
    w: jax.Array,
    beta: jax.Array,
    block_n: int = 1024,
    block_m: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel launch: inputs must be pre-padded/aligned (see ops.py).

    x: (N, n) f32, w: (n, m) f32, beta: (N, 1) f32
    -> (cos_sums (1, m), sin_sums (1, m)) f32
    """
    n_pts, feat = x.shape
    m = w.shape[1]
    assert n_pts % block_n == 0 and m % block_m == 0, (n_pts, m)
    grid = (m // block_m, n_pts // block_n)
    return pl.pallas_call(
        _sketch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, feat), lambda i, j: (j, 0)),
            pl.BlockSpec((feat, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, beta)
