"""Percent of the traced window in which the device runs no op:
1 - (union of device op intervals) / window."""

from chipbench import readers


def read(ctx, device_kind):
    return readers.idle_percent(ctx)
