"""Executor: device milliseconds of the ``packed_paged`` programs in the
traced window per 1,000 prompt tokens they prefilled."""

from readings import PREFILL_PROGRAM, prefill_tokens


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, n = ctx.trace.time_of("XLA Modules", PREFILL_PROGRAM)
    tokens = prefill_tokens(ctx.traced)
    return 1e3 * seconds / (tokens / 1e3) if n and tokens else None
