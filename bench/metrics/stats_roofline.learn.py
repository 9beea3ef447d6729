"""Stats kernel (kernels/elm_stats.py): the least time its (P, Q) work
needs at the chip's peaks, over its device time. The kernel's events
carry ``elm_stats_pallas`` in their instruction name."""

from bench import work


def read(ctx):
    seconds = ctx.trace.op_seconds(
        lambda name, kind: kind == "tpu_custom_call" and "elm_stats_pallas" in name
    )
    if seconds <= 0:
        return None
    cfg = ctx.config
    flops, nbytes = work.stats_terms(cfg["Ni"], cfg["D"], cfg["L"], cfg["M"])
    calls = ctx.counters["jobs"] * cfg["V"]
    return 100.0 * calls * work.least_seconds(flops, nbytes, ctx.peak) / seconds
