"""Packed prefill attention kernel: share (%) of its roofline over the
traced prefill ticks (causal pairs over the live context; see
``costs.packed_prefill_kernel``)."""

import costs
from readings import PREFILL_KERNEL, kernel_share


def read(ctx):
    return kernel_share(ctx, PREFILL_KERNEL, lambda t: costs.packed_prefill_kernel(
        ctx.model, t.segments, ctx.itemsize) if t.segments else None)
