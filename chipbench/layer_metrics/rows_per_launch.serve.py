"""Query rows answered per engine launch in the window: the batcher's
`rows_served` over its `batches_run`, counted over the window only."""


def read(trace, ctx, lc):
    if not lc.get("launches"):
        return None
    return lc["rows"] / lc["launches"]
