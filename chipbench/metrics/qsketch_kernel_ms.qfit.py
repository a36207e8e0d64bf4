"""Device time of the Pallas quantized sketch kernel (ops named
quantized_fourier_sketch*), in milliseconds per fit."""

from chipbench import qckm_work, readers


def read(ctx, device_kind):
    return readers.per(qckm_work.kernel_seconds(ctx), ctx.counts.get("fits"), 1e3)
