"""Shared arithmetic of the per-layer metric readers (``metrics/*.py``):
the names by which the trace shows the served programs and kernels, and
the work the traced ticks needed (``costs.py``)."""

from __future__ import annotations

import costs

# XLA module names of the executor's jitted phase programs
DECODE_PROGRAM = r"decode_paged_impl"
PREFILL_PROGRAM = r"packed_paged_impl"
# Pallas kernels as the trace names them
DECODE_KERNEL = r"paged_decode"
PREFILL_KERNEL = r"packed_prefill"


def kernel_share(ctx, kernel, work) -> float | None:
    """Roofline share (%) of ``kernel`` over the traced ticks: the least
    time the chip needs for the work (``work(tick)`` gives one layer's
    FLOPs and bytes, or None for a tick without that kernel) over the
    kernel's time in the trace."""
    if ctx.trace is None:
        return None
    L = ctx.model["num_hidden_layers"]
    ideal = 0.0
    for t in ctx.traced:
        w = work(t)
        if w is not None:
            ideal += L * costs.ideal_seconds(*w, ctx.peaks)
    seconds, n = ctx.trace.time_of("XLA Ops", kernel)
    if not n or ideal <= 0:
        return None
    return 100.0 * ideal / seconds


def prefill_tokens(ticks) -> int:
    return sum(t for k in ticks for _, t in k.segments)
