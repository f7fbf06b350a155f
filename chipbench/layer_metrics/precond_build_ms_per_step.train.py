"""Device ms per training step of the pivoted-Cholesky preconditioner's build
(cold and refresh steps; a warm step reuses the last one): the summed
device time of the window's operations whose op_name lies under the
program's scope `precond_build`, over the window's steps, averaged over
the chips (`chipbench.program_trace`)."""

from chipbench import program_trace


def read(trace, ctx, lc):
    pt = program_trace.for_run(trace, ctx)
    return program_trace.scope_ms_per_step(pt, trace, "precond_build",
                                           lc.get("steps"))
