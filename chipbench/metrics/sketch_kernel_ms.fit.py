"""Device time of the Pallas sketch kernel (events named fourier_sketch*),
in milliseconds per fit."""

from chipbench import readers


def read(ctx, device_kind):
    return readers.per(readers.kernel_seconds(ctx), ctx.counts.get("fits"), 1e3)
