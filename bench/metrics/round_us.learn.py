"""Rounds (core/engine.py ``ConsensusEngine.run``, every mixer arm):
device time under ``dcelm/rounds``, in us an eq. (20) round of the
window's learning jobs."""

from bench import scopes


def read(ctx):
    phases = scopes.for_cell(ctx)
    rounds = sum(ctx.counters["rounds_per_job"])
    if phases is None or phases["rounds"] <= 0 or rounds <= 0:
        return None
    return 1e6 * phases["rounds"] / rounds
