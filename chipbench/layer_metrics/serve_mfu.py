"""The whole serving path's share of the chip's peak: the MXU operations
of the query rows the window answered (`chipbench.counts.predict_ops`,
unpadded) over the window's host-clock time and the peak at the
configuration's compute dtype."""

from chipbench import counts, peaks


def read(trace, ctx, lc):
    cfg = ctx.config
    if not lc.get("rows"):
        return None
    ops = counts.predict_ops(lc["rows"], cfg["n"], cfg["d"],
                             cfg["lanczos_rank"])
    pk = peaks.peaks_for(ctx.devices[0].device_kind)
    peak = peaks.mxu_flops(pk, cfg["compute_dtype"]) * len(ctx.devices)
    return 100.0 * ops / (lc["window_host_s"] * peak)
