"""The most CG iterations any right-hand side needed, per training step:
the mean over the window's steps of `cg_iters_max` on the program's
`repro.mll_step` spans (the iterations the columns applied before their
residual met `cg_tol`, against the fixed trip count whose kernel
traversals the same span carries as `traversals`, and
`kmvm_traversals_per_step.train` reads from the device)."""

from chipbench import program_trace
from chipbench.common import log


def read(trace, ctx, lc):
    pt = program_trace.for_run(trace, ctx)
    spans = [s for s in program_trace.window_spans(pt, trace, "mll_step")
             if "cg_iters_max" in s.stats]
    if not spans:
        return None
    iters = [float(s.stats["cg_iters_max"]) for s in spans]
    trav = [float(s.stats["traversals"]) for s in spans
            if s.stats.get("traversals") is not None]
    log(f"[trace] mll_step spans in the window: {len(spans)}; cg_iters_max "
        f"{iters}; traversals {trav}")
    return sum(iters) / len(iters)
