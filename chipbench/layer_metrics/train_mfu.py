"""The whole training step's share of the chips' peak: the MXU operations
of the work each step of the traced window needed, over the window's
host-clock time, the chips and the peak at the configuration's compute
dtype.

A step is charged the CG iterations its solve needed (the most any
right-hand side applied, `cg_iters_max`, at most `train_cg_iters`), the
warm start's residual and the Eq. 2 backward, from unpadded shapes
(`chipbench.counts`). Iterations a loop executes after every column has
converged are not charged: they show as the gap between
`kmvm_traversals_per_step.train` and `cg_iters_needed_per_step.train`.
So a fixed-trip loop and one that exits early read the same operations
at the same tolerance, and only their time tells them apart. A window
with a step whose count is unknown reads nothing."""

from chipbench import counts, peaks
from chipbench.common import log


def read(trace, ctx, lc):
    cfg = ctx.config
    iters = lc.get("cg_iters")
    if not iters or None in iters:
        return None
    charged = [min(it, cfg["train_cg_iters"]) for it in iters]
    log(f"[trace] train_mfu: CG iterations charged per step {charged}")
    ops = sum(counts.train_step_ops(cfg["n"], cfg["d"], cfg["num_probes"],
                                    mode, it)
              for mode, it in zip(lc["modes"], charged))
    pk = peaks.peaks_for(ctx.devices[0].device_kind)
    peak = peaks.mxu_flops(pk, cfg["compute_dtype"]) * len(ctx.devices)
    return 100.0 * ops / (lc["window_host_s"] * peak)
