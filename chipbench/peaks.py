"""Published per-chip peaks and the roofline count of the sketch kernel.

Only published numbers: a device kind missing from :data:`PEAKS` is an
error, never a default.  No peak is published for the TPU's sin/cos
throughput, so a roofline share of a trig-bound kernel reads low by
construction.
"""

from __future__ import annotations

# Keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,  # bf16 FLOP/s (MXU)
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def sketch_kernel_work(points: int, n: int, m: int, calls: int) -> tuple[float, float]:
    """(FLOP, bytes) the algorithm needs to sketch ``points`` n-dim points
    against m frequencies in ``calls`` kernel calls: the x·W contraction
    (2·points·n·m) and the f32 points read plus the (cos, sin) sums of
    each call written.  The frequency matrix is read once per call."""
    flops = 2.0 * points * n * m
    nbytes = 4.0 * (points * n + calls * (n * m + 2 * m))
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   device_kind: str) -> tuple[float, str]:
    """Percent of the least time the chip could take, and which peak bounds
    it (``"compute"`` or ``"memory"``)."""
    p = peaks(device_kind)
    t_compute = flops / p["flops"]
    t_memory = nbytes / p["hbm_bytes_per_s"]
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
