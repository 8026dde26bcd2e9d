"""Scheduler: mean rows per decode tick over the window's ticks."""


def read(ctx):
    rows = [len(t.ctx) for t in ctx.ticks if t.ctx]
    return sum(rows) / len(rows) if rows else None
