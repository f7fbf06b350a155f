"""Device ms per training step of the CG solve (`repro.core.pcg.pcg`: the
loop's kernel traversals, the warm start's residual, the preconditioner's
applies, and the kernels' operand preparation under them): the summed
device time of the window's operations whose op_name lies under the
program's scope `pcg`, over the window's steps, averaged over the chips
(`chipbench.program_trace`)."""

from chipbench import program_trace


def read(trace, ctx, lc):
    pt = program_trace.for_run(trace, ctx)
    return program_trace.scope_ms_per_step(pt, trace, "pcg",
                                           lc.get("steps"))
