"""Model step: FLOPs the traced ticks' prefill and decode tokens needed,
over the traced window at the chip's bf16 peak (%). Bounds every
kernel's gain: a kernel taken off the path leaves its roofline silent,
this share still counts the whole step."""

import costs


def read(ctx):
    if ctx.trace is None or not ctx.traced:
        return None
    flops = 0.0
    for t in ctx.traced:
        if t.segments:
            flops += costs.prefill_step(ctx.model, t.segments, ctx.itemsize)[0]
        if t.ctx:
            flops += costs.decode_step(ctx.model, t.ctx, ctx.itemsize)[0]
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks.bf16_flops)
