"""Share of its roofline that the fused kernel K_hat @ V reaches in the
training window, averaged over the chips: the least time a chip needs for
what its launches computed (each launch is a block of rows of the chip's
tile against all of the tile's columns; operations and bytes from
unpadded shapes, `chipbench.counts`, priced at the configuration's compute
dtype) over the kernel's summed device time."""

from chipbench import counts, peaks, trace_reduce
from chipbench.common import log


def read(trace, ctx, lc):
    cfg = ctx.config
    d, t = cfg["d"], 1 + cfg["num_probes"]
    _, cols = counts.train_tile(cfg["n"], cfg["mesh"], cfg["mode"])
    pk = peaks.peaks_for(ctx.devices[0].device_kind)
    shares = []
    for chip, events in trace_reduce.op_events(trace,
                                               trace_reduce.KMVM).items():
        rows = [trace_reduce.leading_dim(e) or 0 for e in events]
        secs = sum(e.end - e.start for e in events) * 1e-9
        if secs <= 0 or not sum(rows):
            continue
        least, bound = counts.roofline_seconds(
            sum(counts.kernel_ops(r, cols, d, t) for r in rows),
            sum(counts.kernel_bytes(r, cols, d, t) for r in rows),
            peaks.mxu_flops(pk, cfg["compute_dtype"]), pk.hbm_bytes_per_s)
        log(f"[trace] kmvm on chip {chip}: {len(rows)} launches, "
            f"{sum(rows)} rows x {cols} columns in {secs!r} s, least "
            f"{least!r} s, bound by {bound}")
        shares.append(100.0 * least / secs)
    return sum(shares) / len(shares) if shares else None
