"""Scheduler / prefix cache: share of the prompt tokens of the window's
requests that the prefix cache served (each request's
``Request.cached_tokens`` at admission; a fraction)."""

import measure


def read(ctx):
    win = [r for r in measure.window_requests(ctx.recs, ctx.w0, ctx.w1)
           if r.request is not None and r.t_tok]
    total = sum(r.prompt_len for r in win)
    return sum(r.cached_tokens for r in win) / total if total else None
