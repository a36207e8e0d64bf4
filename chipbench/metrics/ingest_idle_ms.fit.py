"""Device-idle milliseconds per fit inside the program's ckm.ingest spans:
each chunk's update dispatch and its sync."""

from chipbench import program_spans


def read(ctx, device_kind):
    return program_spans.idle_ms_per_fit(ctx, ("ckm.ingest",))
