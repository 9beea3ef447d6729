"""Ring exchange (core/gossip.py ``neighbor_laplacian``: the ppermutes
of an eq. (20) round on the sharded engine, and the Laplacian they
feed): device time under ``dcelm/exchange``, in us a round on one chip
(summed over the chips, then divided by them)."""

from bench import ring


def read(ctx):
    seconds = ring.exchange_s(ctx)
    rounds = sum(ctx.counters["rounds_per_job"])
    if seconds <= 0 or rounds <= 0:
        return None
    return 1e6 * seconds / ctx.chips / rounds
