"""Backend dispatch for the fused gossip round (eq. (20) hot loop).

``fused_gossip_rounds`` / ``fused_gossip_round`` pick the execution
arm the same way the stats/predict planes do:

* TPU (f32 state): the Pallas kernel — the in-kernel multi-round arm
  when the whole state + snapshots fit the VMEM budget, else one
  kernel launch per round under an outer scan.
* everywhere else: the jitted neighbor-list scan fallback
  (``elm_gossip_ref.elm_gossip_scan``), chunked over neighbor slots.

Block knobs: the Pallas arm's node block ``block_v`` (default
``GOSSIP_BLOCK_V``, shrunk by ``fit_block_v`` to the VMEM budget) and
the scan's neighbor-slot ``chunk`` (default ``GOSSIP_SCAN_CHUNK``).

``prefers_dense`` is the degenerate-graph escape hatch: on dense
graphs (d_max ~ V — complete topologies, or any graph at very small
V where the Omega term dominates) the neighbor gather does the same
MACs as the ``(V,V) @ (V, L*M)`` matmul with worse locality, so the
``analysis/roofline.py`` gossip-round model is consulted and the
caller (``mixers.NeighborMixer``) lowers to the dense round program —
the fused and unfused paths become the same executable, speedup 1.0
by identity (the PR 6 convention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.analysis.roofline import gossip_round_terms
from repro.kernels.dispatch import on_tpu, wants_kernel
from repro.kernels.elm_gossip_ref import (
    elm_gossip_scan,
    gossip_round_payload,
)

#: the Pallas arm's node block before ``fit_block_v`` shrinks it to the
#: VMEM budget (``elm_gossip_pallas``'s own default)
GOSSIP_BLOCK_V = 8

#: neighbor slots the scan fallback gathers a step (passed explicitly:
#: ``elm_gossip_scan``'s ``chunk=None`` gathers every slot at once)
GOSSIP_SCAN_CHUNK = 8

#: modeled dense-round slack on TPU: the neighbor arm must beat the
#: dense matmul round by this factor before it is preferred (gathers
#: have worse locality than a matmul at equal FLOPs)
DENSE_SLACK = 1.25

#: off-TPU slack: XLA:CPU lowers the dense round to BLAS GEMMs
#: running near peak while the neighbor gather+contract runs ~4-5x
#: below it (measured on XLA:CPU over hypercube, torus and random
#: geometric graphs when the gossip kernel came in), so the dense arm's
#: zero-edge MACs only lose once the modeled compute ratio clears that
#: efficiency gap
DENSE_SLACK_OFF_TPU = 5.0


def prefers_dense(
    V: int, d_max: int, L: int, M: int, *, slack: float | None = None
) -> bool:
    """True when the dense matmul round is modeled no slower than the
    neighbor-gather round (within ``slack``).

    The two arms stream the same state/Omega bytes (the memory term is
    shared and cancels), so the choice reduces to the compute term: the
    dense round spends ``2 V^2 L M`` extra MACs on zero edges, which
    only matters once it rivals the shared ``2 V L^2 M`` Omega cost —
    i.e. once ``V`` rivals ``L``. Below that (small V, or L large
    relative to V, or complete-ish graphs where fan-in ~ V anyway) the
    dense matmul's locality wins.

    ``slack`` defaults per backend: ``DENSE_SLACK`` on TPU (both arms
    run near the roofline there) and ``DENSE_SLACK_OFF_TPU`` elsewhere
    (a BLAS GEMM is far more efficient per FLOP than a gather, so the
    modeled ratio must clear the measured efficiency gap first).
    """
    if slack is None:
        slack = DENSE_SLACK if on_tpu() else DENSE_SLACK_OFF_TPU
    tn = gossip_round_terms(V, d_max, L, M)["t_compute"]
    td = gossip_round_terms(V, d_max, L, M, dense=True)["t_compute"]
    return td <= slack * tn


def laplacian_prefers_dense(V: int, d_max: int) -> bool:
    """Laplacian-only arm choice (no Omega term): the gather wins only
    on genuinely sparse graphs."""
    return 2 * d_max >= V


_scan_jit = jax.jit(
    elm_gossip_scan,
    static_argnames=("num_rounds", "compress", "chunk"),
)

_round_payload_jit = jax.jit(
    gossip_round_payload, static_argnames=("chunk",)
)


@functools.cache
def _pallas_jit(multiround: bool):
    """The jitted Pallas arm, named after its entry point in traces
    (``jit(elm_gossip_pallas)``, not a ``jit(<unknown>)`` partial)."""
    from repro.kernels import elm_gossip

    if multiround:
        return jax.jit(
            elm_gossip.elm_gossip_pallas_multiround,
            static_argnames=("num_rounds", "compress", "interpret"),
        )
    return jax.jit(
        elm_gossip.elm_gossip_pallas,
        static_argnames=("num_rounds", "compress", "block_v", "interpret"),
    )


def fused_gossip_rounds(
    betas, omegas, idx, w, deg, scale, *, num_rounds, compress=None,
    use_kernel=None, chunk=None, block_v=None, interpret=None,
):
    """num_rounds fused eq. (20) rounds over padded neighbor lists.

    betas (V, L, M), omegas (V, L, L), idx/w (S, V, d_max), deg (S, V)
    — round k mixes with snapshot k % S; scale = gamma / (VC).
    use_kernel: force the Pallas arm (default: TPU and f32 state only).
    """
    V, L, M = betas.shape
    S, _, d_max = idx.shape
    # the kernel accumulates/stores f32 only
    if wants_kernel(use_kernel) and betas.dtype == jnp.float32:
        if chunk is not None:
            raise ValueError(
                "chunk= is the scan fallback's knob; the Pallas arm "
                "takes block_v="
            )
        from repro.kernels.elm_gossip import (
            fit_block_v,
            multiround_vmem_bytes,
        )
        from repro.kernels.vmem import DEFAULT_SCOPED_VMEM, vmem_budget

        interp = (not on_tpu()) if interpret is None else interpret
        if multiround_vmem_bytes(V, L, M, S, d_max) <= DEFAULT_SCOPED_VMEM:
            return _pallas_jit(True)(
                betas, omegas, idx, w, deg, scale, num_rounds=num_rounds,
                compress=compress, interpret=interp,
            )
        bv = fit_block_v(V, L, M, block_v or GOSSIP_BLOCK_V, vmem_budget())
        return _pallas_jit(False)(
            betas, omegas, idx, w, deg, scale, num_rounds=num_rounds,
            compress=compress, block_v=int(bv), interpret=interp,
        )
    if block_v is not None:
        raise ValueError(
            "block_v= is the Pallas arm's knob; the scan fallback "
            "takes chunk="
        )
    return _scan_jit(
        betas, omegas, idx, w, deg, scale,
        num_rounds=num_rounds, compress=compress,
        chunk=int(chunk or GOSSIP_SCAN_CHUNK),
    )


def fused_gossip_round(
    betas, payload, omegas, idx_k, w_k, deg_k, scale, *,
    use_kernel=None, chunk=None, block_v=None, interpret=None,
):
    """One fused round over an explicitly encoded payload.

    The CompressedMixer arm: ``payload`` is the receivers' view of the
    network (e.g. int8-roundtripped replicas x̂, already encoded with
    the round/node key schedule of core/compression.py); the Laplacian
    is formed from it and the update applied to ``betas``. idx_k/w_k:
    (V, d_max) — one already-selected snapshot; deg_k: (V,).
    """
    V, L, M = betas.shape
    if (
        wants_kernel(use_kernel)
        and betas.dtype == jnp.float32
        and payload.dtype == jnp.float32
    ):
        from repro.kernels.elm_gossip import elm_gossip_pallas, fit_block_v
        from repro.kernels.vmem import vmem_budget

        bv = fit_block_v(
            V, L, M, block_v or GOSSIP_BLOCK_V, vmem_budget(), payload=True
        )
        interp = (not on_tpu()) if interpret is None else interpret
        return elm_gossip_pallas(
            betas, omegas, idx_k[None], w_k[None], deg_k[None], scale,
            num_rounds=1, payload=payload, block_v=int(bv),
            interpret=interp,
        )
    return _round_payload_jit(
        betas, payload, omegas, idx_k, w_k, deg_k, scale,
        chunk=int(chunk or GOSSIP_SCAN_CHUNK),
    )
