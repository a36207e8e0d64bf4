"""Universal (dithered) quantization of the sketch — the QCKM subsystem.

Quantized Compressive K-Means (Schellekens & Jacques, 2018) observes that the
sketch survives heavy per-sample quantization: instead of accumulating the
float contribution ``(cos(w_j^T x + xi_j), sin(w_j^T x + xi_j))`` per point,
accumulate only its *universal 1-bit quantization* — the sign of the dithered
phase through the square wave — or a coarse ``b``-bit uniform code.  The
partial sums become **integer** accumulators, which

- keeps the mergeable-monoid contract of ``core.engine`` intact (sum of
  integer codes is associative + commutative, identity = zeros),
- is exactly split-invariant (the code of a point is a deterministic function
  of the point and the per-frequency dither — no per-sample randomness — so
  any batching of the same points yields the *same* integer state),
- shrinks merge traffic: a partial state over ``B`` points needs only
  ``ceil(log2(2*B*S + 1))`` bits per accumulator entry instead of an f32
  (see :func:`state_wire_bytes`), the bandwidth-aware path for the sharded
  backend's ``psum``.

Encoding (per point ``x``, frequency ``w_j``, dither ``xi_j ~ U[0, 2pi)``)::

    theta_j = w_j^T x + xi_j
    1-bit:   q_c = sign(cos theta_j),            q_s = sign(sin theta_j)
    b-bit:   q_c = round(S * cos theta_j),       q_s = round(S * sin theta_j)
             with S = 2**(b-1) - 1 levels per sign

The 1-bit code computes no cos or sin: both signs are read off the quadrant
of ``theta`` (:func:`one_bit_codes`), in XLA and in the Pallas kernels alike.

Decoding (the known E[sign] correction).  The square wave has the Fourier
series ``sign(cos t) = (4/pi) sum_k (-1)^k cos((2k+1) t) / (2k+1)``, so the
mean of signs over the data is, per frequency,

    mean_i sign(cos(theta_ij)) = (4/pi) [ Re(e^{i xi_j} phi(w_j))
                                          - Re(e^{3 i xi_j} phi(3 w_j))/3 + … ]

where ``phi`` is the empirical characteristic function.  Multiplying by
``pi/4`` and rotating the (cos, sin) pair back by the dither ``-xi`` recovers
``phi(w_j)`` — the paper's sketch entry — up to the odd-harmonic leakage
``|phi(3w)|/3 + |phi(5w)|/5 + …``.  For the adapted-radius frequency scale the
characteristic function at ``3w`` is deep in its tail, so the leakage is small;
the uniformly-random dither makes the k>=3 phases incoherent across
frequencies, so what leakage remains behaves as noise rather than bias in the
decoder.  For the ``b``-bit code the correction is ``1/S`` (no square-wave
factor) and the rounding error is bounded by ``1/(2S)`` per entry.

``CLOMPR`` then runs unchanged on the dequantized sketch — the QCKM result is
precisely that the decoder is robust to this residual distortion.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

__all__ = [
    "SketchQuantizer",
    "parse_bits",
    "draw_dither",
    "make_quantizer",
    "quantization_scale",
    "accumulator_capacity",
    "one_bit_codes",
    "quantize_codes",
    "dequantize_sums",
    "state_wire_bytes",
]


def parse_bits(spec: str) -> int | None:
    """Parse a ``CKMConfig.sketch_quantization`` string.

    ``"none"`` -> ``None``; ``"1bit"`` -> 1; ``"4bit"`` -> 4; … up to 16 bits
    (beyond 16 the codes stop being "heavily compressed" and an f32 sketch is
    simpler).  Raises ``ValueError`` on anything else.
    """
    s = spec.strip().lower()
    if s in ("none", "", "float", "off"):
        return None
    if s.endswith("bit"):
        try:
            bits = int(s[:-3].rstrip("-_ "))
        except ValueError:
            bits = -1
        if 1 <= bits <= 16:
            return bits
    raise ValueError(
        f"sketch_quantization must be 'none', '1bit', or '<b>bit' (b<=16); "
        f"got {spec!r}"
    )


def quantization_scale(bits: int) -> int:
    """Integer levels per sign: 1 for the 1-bit sign code, ``2**(b-1)-1`` else."""
    return 1 if bits == 1 else (1 << (bits - 1)) - 1


def accumulator_capacity(bits: int) -> int:
    """Max number of points an int32 accumulator holds without overflow.

    Worst case every point contributes a full-scale code, so the capacity is
    ``(2**31 - 1) // scale``: the whole int32 range at 1 bit (~2.1e9 points),
    ~16.9M points at 8 bits, ~65k at 16.  The engine's ``finalize`` checks
    the folded count against this bound — beyond it the integer sums would
    wrap silently and the dequantized sketch would be garbage.
    """
    return (2**31 - 1) // quantization_scale(bits)


def draw_dither(key: jax.Array, m: int) -> jax.Array:
    """Per-frequency dither ``xi ~ U[0, 2pi)^m``, shared encoder/decoder."""
    return jax.random.uniform(key, (m,), jnp.float32, 0.0, 2.0 * math.pi)


@dataclasses.dataclass(frozen=True)
class SketchQuantizer:
    """Universal quantizer for one frequency matrix: ``bits`` + fixed dither.

    Holds everything the decoder needs to undo the encoding: the bit depth
    (static) and the per-frequency dither (an ``(m,)`` array drawn once with
    :func:`draw_dither` and reused by every update and by ``dequantize``).
    Pass instances to ``SketchEngine(..., quantizer=...)`` — do **not** mark
    them as jit-static (the dither is a traced array).
    """

    bits: int
    dither: jax.Array  # (m,) f32, xi ~ U[0, 2pi)

    @property
    def scale(self) -> int:
        return quantization_scale(self.bits)


def make_quantizer(key: jax.Array, m: int, spec: str) -> SketchQuantizer | None:
    """``spec`` string -> quantizer (or ``None`` for the float path)."""
    bits = parse_bits(spec)
    if bits is None:
        return None
    return SketchQuantizer(bits=bits, dither=draw_dither(key, m))


# pi/2 split into three float32 constants (Cody-Waite).  The first two have
# at most 10 significant bits, so ``k * C`` is exact in float32 for
# |k| < 2**14; what the three leave of pi/2 is 1.7e-15.
_HALF_PI_1 = 1.5703125
_HALF_PI_2 = 4.837512969970703125e-4
_HALF_PI_3 = 7.549790126404332e-08
_TWO_OVER_PI = 2.0 / math.pi


def one_bit_codes(theta: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int32 ``(sign(cos theta), sign(sin theta))`` of float32 phases, 0 -> +1.

    Both signs depend only on the quadrant of ``theta``, so no cos or sin
    is computed.  ``k0 = floor(theta * 2/pi + 1/2)`` is the nearest multiple
    of pi/2 up to rounding; ``r = theta - k0 * pi/2``, reduced against the
    three-part pi/2, then lies well inside (-pi/2, pi/2) and its sign is
    exact, so the quadrant is ``k = k0 - (r < 0)``.  ``cos`` is >= 0 in
    quadrants 0 and 3 (``(k + 1) & 2 == 0``), ``sin`` in quadrants 0 and 1
    (``k & 2 == 0``); two's complement ``&`` gives negative ``k`` the same
    period.  Only float32 mul/sub/floor, compares, selects, a float->int32
    convert and int32 ``&``/add: ops that Mosaic and XLA round alike, so the
    Pallas kernels and the XLA path make the same codes bit for bit.
    """
    k0 = jnp.floor(theta * _TWO_OVER_PI + 0.5)
    r = ((theta - k0 * _HALF_PI_1) - k0 * _HALF_PI_2) - k0 * _HALF_PI_3
    k = k0.astype(jnp.int32)
    k = jnp.where(r < 0, k - 1, k)
    qc = jnp.where(((k + 1) & 2) == 0, 1, -1).astype(jnp.int32)
    qs = jnp.where((k & 2) == 0, 1, -1).astype(jnp.int32)
    return qc, qs


def quantize_codes(
    proj: jax.Array, dither: jax.Array, bits: int, valid: jax.Array | None = None
) -> tuple[jax.Array, jax.Array]:
    """Integer codes of one projection block.

    ``proj``: (..., m) raw phases ``x @ W``; ``dither``: (m,).  Returns int32
    ``(q_cos, q_sin)`` of the same shape.  The 1-bit code comes from the
    phase's quadrant (:func:`one_bit_codes`, no cos or sin), as in the
    Pallas kernels; the b-bit code rounds ``S * cos`` and ``S * sin``.
    ``valid`` (broadcastable 0/1 mask) zeroes padding rows so they cannot
    shift the integer sums.
    """
    theta = proj + dither
    if bits == 1:
        qc, qs = one_bit_codes(theta)
    else:
        c, s = jnp.cos(theta), jnp.sin(theta)
        scale = float(quantization_scale(bits))
        qc = jnp.round(c * scale).astype(jnp.int32)
        qs = jnp.round(s * scale).astype(jnp.int32)
    qc = qc.astype(jnp.int32)
    qs = qs.astype(jnp.int32)
    if valid is not None:
        v = valid.astype(jnp.int32)
        qc = qc * v
        qs = qs * v
    return qc, qs


def dequantize_sums(
    qcos: jax.Array,
    qsin: jax.Array,
    dither: jax.Array,
    bits: int,
) -> tuple[jax.Array, jax.Array]:
    """E[sign] correction: integer sums -> float ``(cos_acc, sin_acc)`` sums.

    Returns unnormalised float accumulators equivalent to the unquantized
    state's ``(sum cos(w^T x), sum sin(w^T x))`` so the engine's ``finalize``
    is shared (it divides by ``weight_sum`` as for float states): correction
    factor (``pi/4`` for 1-bit, ``1/S`` for b-bit), then a joint rotation by
    ``-xi`` undoes the dither exactly.
    """
    corr = math.pi / 4.0 if bits == 1 else 1.0 / quantization_scale(bits)
    sc = corr * qcos.astype(jnp.float32)  # ~ sum cos(theta + xi)
    ss = corr * qsin.astype(jnp.float32)  # ~ sum sin(theta + xi)
    cd, sd = jnp.cos(dither), jnp.sin(dither)
    cos_sum = cd * sc + sd * ss  # cos(t) = cos(t+xi)cos(xi) + sin(t+xi)sin(xi)
    sin_sum = cd * ss - sd * sc
    return cos_sum, sin_sum


def state_wire_bytes(m: int, count: int, bits: int | None) -> int:
    """Bytes-on-the-wire of one partial state's accumulators.

    The merge traffic of the sharded backend is dominated by the two ``(m,)``
    accumulators.  Float states ship ``2*m`` f32s.  A quantized partial over
    ``count`` points has entries in ``[-count*S, count*S]``, so the minimal
    integer width is ``ceil(log2(2*count*S + 1))`` bits, rounded up to the
    nearest {1, 2, 4}-byte lane type actually available on the interconnect.
    """
    if bits is None:
        return 2 * m * 4
    span = 2 * max(int(count), 1) * quantization_scale(bits) + 1
    needed_bits = max(8, math.ceil(math.log2(span)))
    width = next((w for w in (1, 2, 4) if 8 * w >= needed_bits), 8)
    return 2 * m * width
