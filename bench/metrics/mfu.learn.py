"""Whole learning job (stats, Cholesky Omega, rounds): the useful FLOPs
the window's jobs need, over the window, the chips and the bf16 peak."""


def read(ctx):
    peak = ctx.chips * ctx.peak["bf16_flops"]
    return 100.0 * ctx.counters["useful_flops"] / (ctx.trace.window_s * peak)
