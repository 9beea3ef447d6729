"""Device: share of the streaming window in which no program ran."""


def read(ctx):
    return 100.0 * ctx.trace.idle_share
