"""Omega (core/stats.py ``omega_from_moments``, Cholesky) and the seed
beta_i = Omega_i Q_i (core/online.py ``reseed_betas``), the work that
``work.omega_flops`` counts: device time under ``dcelm/omega`` and
``dcelm/reseed``, in ms a learning job."""

from bench import scopes


def read(ctx):
    phases = scopes.for_cell(ctx)
    if phases is None or phases["omega"] <= 0:
        return None
    seconds = phases["omega"] + phases["reseed"]
    return 1e3 * seconds / ctx.counters["jobs"]
