"""Device-idle milliseconds per fit inside the program's ckm.decode spans:
the host's decode dispatch, its retrace and its cache loads."""

from chipbench import program_spans


def read(ctx, device_kind):
    return program_spans.idle_ms_per_fit(ctx, ("ckm.decode",))
