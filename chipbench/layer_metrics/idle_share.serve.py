"""Share of the serving window in which no operation runs on the chip
(1 - busy / window, busy the union of the device operations' intervals)."""

from chipbench import trace_reduce


def read(trace, ctx, lc):
    share = trace_reduce.idle_share(trace)
    return None if share is None else 100.0 * share
