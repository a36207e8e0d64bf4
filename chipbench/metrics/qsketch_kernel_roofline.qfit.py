"""The quantized sketch kernel's share of its roofline, in percent: the
least time the chip needs for the fits' FLOP and bytes
(chipbench.qckm_work, from shapes; chipbench.peaks, published peaks) over
the kernel's device time."""

from chipbench import peaks, qckm_work


def read(ctx, device_kind):
    secs = qckm_work.kernel_seconds(ctx)
    fits = ctx.counts.get("fits")
    if secs is None or not fits:
        return None
    cfg = ctx.cell.config
    flops, nbytes = qckm_work.qsketch_kernel_work(
        fits * ctx.counts["points_per_fit"], cfg["n"], cfg["m"],
        fits * ctx.counts["kernel_calls_per_fit"])
    share, _ = peaks.roofline_share(flops, nbytes, secs, device_kind)
    return share
