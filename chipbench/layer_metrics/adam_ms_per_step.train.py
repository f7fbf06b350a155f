"""Host ms per training step of the optimizer update: the mean duration
of the window's `repro.adam_update` spans (`repro.optim.adam_update`,
whose eager per-leaf operations keep the device idle while they are
dispatched)."""

from chipbench import program_trace


def read(trace, ctx, lc):
    pt = program_trace.for_run(trace, ctx)
    spans = program_trace.window_spans(pt, trace, "adam_update")
    if not spans:
        return None
    return 1e-6 * sum(s.end - s.start for s in spans) / len(spans)
