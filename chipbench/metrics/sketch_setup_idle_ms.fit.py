"""Device-idle milliseconds per fit inside the program's ckm.sigma2 and
ckm.operator spans: the sigma^2 estimate and the operator draw."""

from chipbench import program_spans


def read(ctx, device_kind):
    return program_spans.idle_ms_per_fit(ctx, ("ckm.sigma2", "ckm.operator"))
