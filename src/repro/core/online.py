"""Online DC-ELM — the paper's Algorithm 2.

When a node's local data changes by a chunk (add DeltaS+ / remove
DeltaS-), the frozen preconditioner Omega_i = (I/(VC) + P_i)^{-1} and the
moment Q_i are updated in O(L^2 * DeltaN) via Sherman-Morrison-Woodbury
(paper eqs. 23-28) instead of re-inverting in O(L^3):

  remove (eq. 26):  Omega <- Omega + Omega dH^T (I_dN - dH Omega dH^T)^{-1} dH Omega
  add    (eq. 27):  Omega <- Omega - Omega dH^T (I_dN + dH Omega dH^T)^{-1} dH Omega
  and Q <- Q -/+ dH^T dT.

After the stat update, beta_i is re-seeded at the new local optimum
beta_i = Omega_i Q_i (Algorithm 2 step 13) — which restores the
zero-gradient-sum invariant — and consensus rounds resume.

This module owns the node-local statistics algebra only. The driver
that applies it across the network — batching the updates over the
stacked node axis, re-seeding, and running the consensus rounds on
either mixer — is ``engine.ConsensusEngine.stream_chunk`` (with
``stream_leave``/``stream_join`` handling whole-node churn via
``rescale_num_nodes``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import stats as stats_lib
from repro.core.scopes import phase


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OnlineNodeState:
    """One node's online-ELM sufficient statistics.

    omega: (L, L) current (I/(VC) + P)^{-1}
    Q:     (L, M) current H^T T
    """

    omega: jax.Array
    Q: jax.Array

    @property
    def beta(self) -> jax.Array:
        return jnp.matmul(self.omega, self.Q, precision="highest")


def init_state(H: jax.Array, T: jax.Array, C: float, V: int) -> OnlineNodeState:
    """Warm-up statistics via the statistics plane (Cholesky Omega)."""
    P_, Q_ = stats_lib.hidden_moments(H, T)
    return OnlineNodeState(
        omega=stats_lib.omega_from_moments(P_, C, V), Q=Q_
    )


def woodbury_add(omega: jax.Array, dH: jax.Array) -> jax.Array:
    """Rank-dN downdate of the inverse after ADDING rows dH (eq. 27)."""
    dN = dH.shape[0]
    K = jnp.matmul(omega, dH.T, precision="highest")
    S = jnp.eye(dN, dtype=omega.dtype) + jnp.matmul(dH, K, precision="highest")
    return omega - jnp.matmul(K, jnp.linalg.solve(S, K.T), precision="highest")


def woodbury_remove(omega: jax.Array, dH: jax.Array) -> jax.Array:
    """Rank-dN update of the inverse after REMOVING rows dH (eq. 26)."""
    dN = dH.shape[0]
    K = jnp.matmul(omega, dH.T, precision="highest")
    S = jnp.eye(dN, dtype=omega.dtype) - jnp.matmul(dH, K, precision="highest")
    return omega + jnp.matmul(K, jnp.linalg.solve(S, K.T), precision="highest")


@jax.jit
def remove_chunk(state: OnlineNodeState, dH: jax.Array, dT: jax.Array):
    """Algorithm 2, steps 5-8."""
    with phase("woodbury"):
        return OnlineNodeState(
            omega=woodbury_remove(state.omega, dH),
            Q=state.Q - jnp.matmul(dH.T, dT, precision="highest"),
        )


@jax.jit
def add_chunk(state: OnlineNodeState, dH: jax.Array, dT: jax.Array):
    """Algorithm 2, steps 9-12."""
    with phase("woodbury"):
        return OnlineNodeState(
            omega=woodbury_add(state.omega, dH),
            Q=state.Q + jnp.matmul(dH.T, dT, precision="highest"),
        )


def update_chunk(
    state: OnlineNodeState,
    added: tuple[jax.Array, jax.Array] | None = None,
    removed: tuple[jax.Array, jax.Array] | None = None,
) -> OnlineNodeState:
    """Apply remove-then-add, the paper's Algorithm 2 ordering."""
    if removed is not None:
        state = remove_chunk(state, *removed)
    if added is not None:
        state = add_chunk(state, *added)
    return state


def vertical_chunk(
    state: OnlineNodeState,
    X_new_slices,
    T_new: jax.Array,
    feature_map,
    *,
    remove: bool = False,
    graph=None,
    secure=None,
    faults=None,
    **kw,
):
    """Node-local Algorithm 2 update from column-sliced new rows.

    The chunk's rows arrive at every node at once (vertical mode: same
    samples, disjoint columns), so the assembled hidden chunk dH is
    shared — each node folds (dH/sqrt(V), dT/sqrt(V)) into its state,
    preserving the per-node stats = network-total/V invariant that the
    vertical init establishes. Reduction keywords (``secure=``,
    ``faults=``, ``start_round=``) pass through to
    ``core.vertical.reduce_partials``.

    Returns (OnlineNodeState, ReduceReport). For the full networked
    driver (consensus rounds included) use ``vertical.stream_chunk``.
    """
    from repro.core import vertical
    from repro.core.consensus import complete
    from repro.core.features import ACTIVATIONS

    vfmap = feature_map
    if graph is None:
        graph = complete(vfmap.num_nodes)
    partials = [
        vfmap.partial_preactivation(i, x)
        for i, x in enumerate(X_new_slices)
    ]
    dZ, report = vertical.reduce_partials(
        partials, graph, secure=secure, faults=faults, **kw
    )
    dH = ACTIVATIONS[vfmap.activation](dZ + vfmap.bias)
    if T_new.ndim == 1:
        T_new = T_new[:, None]
    scale = 1.0 / jnp.sqrt(jnp.asarray(float(graph.num_nodes), dH.dtype))
    chunk = (dH * scale, T_new.astype(dH.dtype) * scale)
    new = update_chunk(
        state,
        added=None if remove else chunk,
        removed=chunk if remove else None,
    )
    return new, report


# Batched (all V nodes at once) variants, used by the streaming driver
# ``ConsensusEngine.stream_chunk`` (engine.py).
batched_add_chunk = jax.jit(jax.vmap(add_chunk))
batched_remove_chunk = jax.jit(jax.vmap(remove_chunk))


def rescale_num_nodes(
    omega: jax.Array, V_old: int, V_new: int, C: float
) -> jax.Array:
    """Re-target Omega = (I/(V_old C) + P)^{-1} to a new network size.

    Elastic membership changes V, and V sits inside every node's frozen
    preconditioner through the ridge term I/(VC). The shift is
    delta * I with delta = 1/(V_new C) - 1/(V_old C), i.e. a rank-L
    identity "chunk": reuse the same Woodbury identities as data
    add/remove with dH = sqrt(|delta|) * I_L (add when the ridge
    stiffens — a node left — remove when it relaxes — a node joined).
    """
    if V_old == V_new:
        return omega
    delta = (1.0 / V_new - 1.0 / V_old) / C
    L = omega.shape[-1]
    dH = jnp.sqrt(jnp.asarray(abs(delta), omega.dtype)) * jnp.eye(
        L, dtype=omega.dtype
    )
    if delta > 0:
        return woodbury_add(omega, dH)
    return woodbury_remove(omega, dH)


batched_rescale_num_nodes = jax.jit(
    jax.vmap(rescale_num_nodes, in_axes=(0, None, None, None)),
    static_argnums=(1, 2, 3),
)


def reseed_betas(states: OnlineNodeState) -> jax.Array:
    """Stacked beta_i = Omega_i Q_i after an online update (step 13)."""
    with phase("reseed"):
        return jnp.einsum(
            "vlk,vkm->vlm", states.omega, states.Q, precision="highest"
        )


@functools.partial(jax.jit, static_argnames=("C", "V"))
def direct_state(H: jax.Array, T: jax.Array, C: float, V: int) -> OnlineNodeState:
    """O(L^3) recompute-from-scratch reference for the Woodbury paths."""
    return init_state(H, T, C, V)
