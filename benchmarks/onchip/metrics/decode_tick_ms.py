"""Executor: device milliseconds per ``decode_paged`` program call in the
traced window."""

from readings import DECODE_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, n = ctx.trace.time_of("XLA Modules", DECODE_PROGRAM)
    return 1e3 * seconds / n if n else None
