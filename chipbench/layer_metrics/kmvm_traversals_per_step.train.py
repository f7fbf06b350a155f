"""Full passes of the fused kernel over a chip's tile of K_hat per
training step, from the trace: the rows its launches computed over the
tile's rows, averaged over the chips. This is the count the device
executed, not the work the step needed: a fixed-trip CG loop runs its
whole trip count whether or not the columns have converged, and
`train_mfu` charges only the iterations they needed
(`cg_iters_needed_per_step.train`), so the gap between the two is the
executed waste."""

from chipbench import counts, trace_reduce


def read(trace, ctx, lc):
    rows = trace_reduce.op_rows(trace, trace_reduce.KMVM)
    if not lc.get("steps") or not any(rows.values()):
        return None
    cfg = ctx.config
    tile_rows, _ = counts.train_tile(cfg["n"], cfg["mesh"], cfg["mode"])
    return sum(rows.values()) / len(rows) / tile_rows / lc["steps"]
