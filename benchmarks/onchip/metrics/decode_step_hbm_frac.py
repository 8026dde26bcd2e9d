"""Model step: bytes the traced decode steps needed (every matmul weight
and the head once, the live KV once) at the chip's HBM peak, over the
device time of their ``decode_paged`` programs (a fraction)."""

import costs
from readings import DECODE_PROGRAM


def read(ctx):
    if ctx.trace is None:
        return None
    seconds, n = ctx.trace.time_of("XLA Modules", DECODE_PROGRAM)
    need = sum(costs.decode_step(ctx.model, t.ctx, ctx.itemsize)[1]
               for t in ctx.traced if t.ctx)
    if not n or need <= 0:
        return None
    return need / ctx.peaks.hbm_bytes_s / seconds
