"""The work of the quantized sketch kernel, for its roofline share.

``quantized_fourier_sketch_kernel`` (``kernels/fourier_sketch.py``) computes
the dithered phases ``x·W + xi`` of every point, takes the 1-bit codes of
their cos and sin, and sums them as int32.  Counted from shapes: the x·W
contraction, 2·N·n·m FLOP; the f32 points and their f32 valid column read,
4·N·n + 4·N bytes; per call the f32 frequencies and dither read, 4·n·m +
4·m bytes, and the two int32 code sums written, 8·m bytes.  As with the
float kernel (``peaks.sketch_kernel_work``), the share reads low: the time
goes to cos/sin, for which no peak is published.
"""

from __future__ import annotations

from chipbench import readers
from chipbench import trace_reduce as tr

KERNEL = "quantized_fourier_sketch"  # device ops quantized_fourier_sketch_kernel.<i>


def qsketch_kernel_work(points: int, n: int, m: int, calls: int) -> tuple[float, float]:
    """(FLOP, bytes) of sketching ``points`` n-dim points against m
    frequencies in ``calls`` kernel calls (module docstring)."""
    flops = 2.0 * points * n * m
    nbytes = 4.0 * (points * n + points) + calls * (4.0 * (n * m + m) + 8.0 * m)
    return flops, nbytes


def kernel_seconds(ctx) -> float | None:
    """Device seconds of the quantized kernel's ops in the traced window."""
    w = readers.window(ctx)
    if w is None:
        return None
    s = tr.op_seconds(ctx.trace_data, *w, lambda name: name.startswith(KERNEL))
    return s if s > 0 else None
