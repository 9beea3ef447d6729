"""Predict kernel (kernels/elm_predict.py): the least time of the rows
served at the chip's peaks, over the device time of the Pallas kernels,
which in a serving window are the predict kernel's launches alone."""

from bench import work
from bench.trace import is_kernel


def read(ctx):
    seconds = ctx.trace.op_seconds(is_kernel)
    if seconds <= 0:
        return None
    c, cfg = ctx.counters, ctx.config
    flops, nbytes = work.predict_terms(
        c["rows"], c["batches"], cfg["D"], cfg["L"], cfg["M"]
    )
    return 100.0 * work.least_seconds(flops, nbytes, ctx.peak) / seconds
