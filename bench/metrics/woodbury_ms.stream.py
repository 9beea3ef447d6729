"""Chunk update (the feature map over the new rows, core/online.py
``add_chunk`` / ``remove_chunk`` and ``reseed_betas``): device time under
``dcelm/features``, ``dcelm/woodbury`` and ``dcelm/reseed``, in ms a
streamed chunk."""

from bench import scopes


def read(ctx):
    phases = scopes.for_cell(ctx)
    if phases is None or phases["woodbury"] <= 0:
        return None
    seconds = phases["features"] + phases["woodbury"] + phases["reseed"]
    return 1e3 * seconds / ctx.counters["chunks"]
