"""Paged decode attention kernel: share (%) of its roofline over the
traced decode ticks (live context only; see ``costs.paged_decode_kernel``)."""

import costs
from readings import DECODE_KERNEL, kernel_share


def read(ctx):
    return kernel_share(ctx, DECODE_KERNEL, lambda t: costs.paged_decode_kernel(
        ctx.model, t.ctx, ctx.itemsize) if t.ctx else None)
