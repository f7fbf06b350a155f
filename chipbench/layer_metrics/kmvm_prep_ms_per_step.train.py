"""Device ms per training step of the fused kernel's per-launch operand
preparation (`repro.kernels.ops`: X and V scaled by the lengthscale, cast,
and padded to the launch's tiles and 128 lanes): the summed device time of
the window's operations whose op_name lies under the program's scope
`kmvm.prep`, over the window's steps, averaged over the chips
(`chipbench.program_trace`)."""

from chipbench import program_trace


def read(trace, ctx, lc):
    pt = program_trace.for_run(trace, ctx)
    return program_trace.scope_ms_per_step(pt, trace, "kmvm.prep",
                                           lc.get("steps"))
