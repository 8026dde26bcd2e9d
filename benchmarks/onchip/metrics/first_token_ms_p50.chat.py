"""Executor: median over the window's requests of the time from each one's
first admission to its first token (``Request.t_first_token -
Request.t_admit``; ms): its prefill, and the ticks it shared. A request
with no first token counts as infinite, as in ``ttft_p50_ms``."""

from lifecycle import median_ms


def read(ctx):
    return median_ms(ctx, "t_admit", "t_first_token")
