"""Share of their roofline that the serving kernels reach in the window:
the least time the chip needs for the query rows the window answered
(each row against all n training points, once for the mean with one
column and once for the variance with lanczos_rank columns; operations
and bytes from unpadded shapes, `chipbench.counts`) over the summed
device time of the fused kernels' launches. Padded query rows count as
waste."""

from chipbench import counts, peaks, trace_reduce
from chipbench.common import log


def read(trace, ctx, lc):
    cfg = ctx.config
    secs = sum(trace_reduce.op_time_s(trace, trace_reduce.KMVM).values())
    if secs <= 0 or not lc.get("rows"):
        return None
    rows, n, d, r = lc["rows"], cfg["n"], cfg["d"], cfg["lanczos_rank"]
    pk = peaks.peaks_for(ctx.devices[0].device_kind)
    least, bound = counts.roofline_seconds(
        counts.predict_ops(rows, n, d, r),
        lc["launches"] * counts.predict_bytes(0, n, d, r)
        + counts.predict_bytes(rows, 0, d, r),
        peaks.mxu_flops(pk, cfg["compute_dtype"]), pk.hbm_bytes_per_s)
    log(f"[trace] serving kernels: {secs!r} s on device for {rows} rows, "
        f"least {least!r} s, bound by {bound}")
    return 100.0 * least / secs
