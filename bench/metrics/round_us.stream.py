"""Rounds (core/engine.py ``ConsensusEngine.run``, here the neighbor
gossip kernel): device time under ``dcelm/rounds``, in us an eq. (20)
round of the window's streamed chunks."""

from bench import scopes


def read(ctx):
    phases = scopes.for_cell(ctx)
    rounds = ctx.counters["rounds"]
    if phases is None or phases["rounds"] <= 0 or rounds <= 0:
        return None
    return 1e6 * phases["rounds"] / rounds
