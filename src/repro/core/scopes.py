"""Device-side names of the DC-ELM phases.

``phase(name)`` names the ops traced inside it: a ``jax.named_scope``
under ``dcelm/``, which each HLO instruction carries in its ``op_name``
metadata, so that a device profile can sum time by phase. The function
that does a phase's work wraps its body in it, so every caller inherits
the name. An op belongs to the innermost ``dcelm/`` component of its
``op_name``.

The phase also goes on each op as the frontend attribute
``dcelm_phase``. JAX's persistent compile cache strips debug info, and
with it the named scopes, from its key; without the attribute, a program
compiled by a build without the scopes would be served to a build with
them, from a shared cache, and its ops would carry no phase. Both are
names only: the compiled programs are the same without them, apart from
names.

    features  RandomFeatureMap / RBFFeatureMap ``__call__``
    stats     stats.raw_moments (the fused kernel, its pads and copies)
    omega     stats.omega_from_moments (Cholesky Omega)
    reseed    online.reseed_betas (beta_i = Omega_i Q_i)
    woodbury  online.add_chunk / remove_chunk (Algorithm 2 updates)
    rounds    ConsensusEngine.run (eq. (20) rounds, every mixer arm)
    exchange  gossip.neighbor_laplacian and its masked variant (the
              ppermute exchange with the mesh neighbors, nested in
              ``rounds`` on the sharded path)
"""

from __future__ import annotations

import contextlib

import jax
from jax.experimental.xla_metadata import set_xla_metadata

PREFIX = "dcelm/"
PHASES = (
    "features", "stats", "omega", "reseed", "woodbury", "rounds", "exchange",
)
ATTRIBUTE = "dcelm_phase"


def phase(name: str):
    """The named scope of one DC-ELM phase (a name in ``PHASES``)."""
    if name not in PHASES:
        raise ValueError(f"unknown DC-ELM phase {name!r}; phases: {PHASES}")
    return _named(name)


@contextlib.contextmanager
def _named(name: str):
    with jax.named_scope(PREFIX + name), set_xla_metadata(**{ATTRIBUTE: name}):
        yield
