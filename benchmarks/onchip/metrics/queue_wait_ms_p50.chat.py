"""Scheduler: median over the window's requests of the time each waited
in the engine before its first admission to a slot (``Request.t_admit -
Request.t_submit``; ms). A request never admitted counts as infinite, as
in ``ttft_p50_ms``."""

from lifecycle import median_ms


def read(ctx):
    return median_ms(ctx, "t_submit", "t_admit")
