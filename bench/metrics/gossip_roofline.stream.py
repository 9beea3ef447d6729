"""Gossip kernel (kernels/elm_gossip.py): the least time of the window's
eq. (20) rounds at the chip's peaks (the gossip-round model's FLOPs and
bytes), over the device time of the Pallas kernels, which in a streaming
window are the gossip kernel's launches alone."""

from bench import work
from bench.trace import is_kernel


def read(ctx):
    seconds = ctx.trace.op_seconds(is_kernel)
    if seconds <= 0:
        return None
    c, cfg = ctx.counters, ctx.config
    terms = work.gossip_round_terms(c["V"], c["d_max"], cfg["L"], cfg["M"])
    least = work.least_seconds(terms["flops"], terms["hbm_bytes"], ctx.peak)
    return 100.0 * c["rounds"] * least / seconds
