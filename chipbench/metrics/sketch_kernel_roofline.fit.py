"""The sketch kernel's share of its roofline, in percent: the least time the
chip needs for the fits' 2·N·n·m FLOP and their bytes (chipbench.peaks, from
shapes and published peaks) over the kernel's device time."""

from chipbench import peaks, readers


def read(ctx, device_kind):
    secs = readers.kernel_seconds(ctx)
    fits = ctx.counts.get("fits")
    if secs is None or not fits:
        return None
    cfg = ctx.cell.config
    flops, nbytes = peaks.sketch_kernel_work(
        fits * ctx.counts["points_per_fit"], cfg["n"], cfg["m"],
        fits * ctx.counts["kernel_calls_per_fit"])
    share, _ = peaks.roofline_share(flops, nbytes, secs, device_kind)
    return share
