"""Engine (core/engine.py): eq. (20) rounds a streamed chunk ran before
the residual reached epsilon again, the mean over the window's chunks."""


def read(ctx):
    rounds = ctx.counters["rounds_per_chunk"]
    return sum(rounds) / len(rounds)
