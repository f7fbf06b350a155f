"""The whole training step's share of the chips' peak: the MXU operations
of the configured BBMM steps the traced window ran (every CG traversal,
the warm start's residual and the Eq. 2 backward, from unpadded shapes,
`chipbench.counts`) over the window's host-clock time, the chips and the
peak at the configuration's compute dtype."""

from chipbench import counts, peaks


def read(trace, ctx, lc):
    cfg = ctx.config
    if not lc.get("steps"):
        return None
    ops = sum(counts.train_step_ops(cfg["n"], cfg["d"], cfg["num_probes"],
                                    mode, cfg["train_cg_iters"])
              for mode in lc["modes"])
    pk = peaks.peaks_for(ctx.devices[0].device_kind)
    peak = peaks.mxu_flops(pk, cfg["compute_dtype"]) * len(ctx.devices)
    return 100.0 * ops / (lc["window_host_s"] * peak)
