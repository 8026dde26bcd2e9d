"""Front end: median time to first token of the window's requests, on
the client's clock from when each was sent (a failed request counts as
infinite)."""

import math

import measure


def read(ctx):
    tt = measure.ttfts(ctx.recs, ctx.w0, ctx.w1)
    v = 1e3 * measure.quantile(tt, 0.5) if tt else None
    return v if v is not None and math.isfinite(v) else None
