"""Ring exchange: the share of the rounds' device time (``dcelm/rounds``,
the exchange nested in it included) that lies under ``dcelm/exchange``,
over every chip."""

from bench import ring, scopes


def read(ctx):
    seconds = ring.exchange_s(ctx)
    phases = scopes.for_cell(ctx)
    if seconds <= 0 or phases is None or phases["rounds"] <= 0:
        return None
    return 100.0 * seconds / phases["rounds"]
