"""Medians of the request-lifecycle intervals the program stamps on each
``Request`` (``t_submit``, ``t_admit``, ``t_first_token``: the engine's
clock), over the window's requests."""

from __future__ import annotations

import math
from typing import Optional

import measure


def median_ms(ctx, since: str, until: str) -> Optional[float]:
    """Median of ``until - since`` over the window's requests, in ms. A
    request with either stamp unset (never admitted, no first token,
    refused at submit) counts as infinite. None where the program stamps
    no such field, or where the median is infinite."""
    win = measure.window_requests(ctx.recs, ctx.w0, ctx.w1)
    reqs = [r.request for r in win if r.request is not None]
    if not win or not all(hasattr(q, since) and hasattr(q, until)
                          for q in reqs):
        return None
    waits = [math.inf] * (len(win) - len(reqs)) + [
        getattr(q, until) - getattr(q, since)
        if getattr(q, since) > 0 and getattr(q, until) > 0 else math.inf
        for q in reqs]
    v = 1e3 * measure.quantile(waits, 0.5)
    return v if math.isfinite(v) else None
