"""Whole serving window: the FLOPs of the rows served, over the window,
the chips and the bf16 peak."""


def read(ctx):
    peak = ctx.chips * ctx.peak["bf16_flops"]
    return 100.0 * ctx.counters["useful_flops"] / (ctx.trace.window_s * peak)
