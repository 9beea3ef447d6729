"""The ring exchange's device time, for ``metrics/exchange_*.learn.py``.

On the sharded engine the program names the ppermutes of an eq. (20)
round, and the Laplacian they feed, ``dcelm/exchange``, nested in
``dcelm/rounds`` (``repro.core.scopes``). ``bench/scopes.py`` keeps its
own phase list, which stops at ``rounds``, so the exchange counts there
and is taken apart here.
"""

from __future__ import annotations

import re

from bench import scopes

EXCHANGE = re.compile(r"dcelm/exchange(?![A-Za-z0-9_])")


def exchange_s(ctx) -> float:
    """Device seconds under ``dcelm/exchange`` inside the traced window,
    summed over the chips: ``scopes.Phases`` of the exchange's ops alone,
    so that containers and the window's ends count as they do there. 0
    where the profile holds no such op (a program without the scope)."""
    events = scopes.events_for(scopes.TRACES / ctx.cell)
    if events is None:
        return 0.0
    exchange = {
        chip: [op for op in ops if EXCHANGE.search(op[4])]
        for chip, ops in events["devices"].items()
    }
    return scopes.Phases({"devices": exchange}, ctx.trace.lo, ctx.trace.hi).busy_s
