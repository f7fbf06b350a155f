"""Device ms per training step of the Eq. 2 backward (the gradient's
blockwise quadratic forms and their sums,
`distributed.dist_mll_backward`): the summed device time of the window's
operations whose op_name lies under the program's scope `eq2_backward`,
over the window's steps, averaged over the chips
(`chipbench.program_trace`)."""

from chipbench import program_trace


def read(trace, ctx, lc):
    pt = program_trace.for_run(trace, ctx)
    return program_trace.scope_ms_per_step(pt, trace, "eq2_backward",
                                           lc.get("steps"))
