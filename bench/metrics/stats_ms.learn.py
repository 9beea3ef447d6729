"""Stats pass (core/stats.py ``raw_moments``: the fused kernel, its pads
and copies of X, and any feature map called inside): device time under
``dcelm/stats`` and ``dcelm/features``, in ms a learning job."""

from bench import scopes


def read(ctx):
    phases = scopes.for_cell(ctx)
    if phases is None or phases["stats"] <= 0:
        return None
    seconds = phases["stats"] + phases["features"]
    return 1e3 * seconds / ctx.counters["jobs"]
