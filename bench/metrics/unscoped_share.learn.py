"""Phase names (core/scopes.py): the share of the learning window's busy
op time that lies in no ``dcelm/`` phase."""

from bench import scopes


def read(ctx):
    phases = scopes.for_cell(ctx)
    if phases is None:
        return None
    return 100.0 * phases.unscoped_s / phases.busy_s
