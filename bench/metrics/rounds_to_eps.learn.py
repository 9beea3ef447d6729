"""Engine (core/engine.py): eq. (20) rounds a learning job ran before
its consensus residual reached epsilon, the mean over the window's jobs."""


def read(ctx):
    rounds = ctx.counters["rounds_per_job"]
    return sum(rounds) / len(rounds)
