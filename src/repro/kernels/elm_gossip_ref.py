"""Reference + scan-fallback implementations of the fused gossip round.

The consensus plane's hot loop is the paper's eq. (20) update

    beta_i += (gamma / VC) * Omega_i @ lap_i,
    lap_i   = sum_{j in N_i} a_ij (beta_j - beta_i),

which ``core/mixers.DenseMixer`` evaluates as a dense ``(V, V) @
(V, L*M)`` matmul — V^2 work even for hypercube/ring graphs whose
degree is ~log V. This module is the neighbor-sparse formulation over a
padded CSR-style neighbor list: per node, ``d_max`` neighbor slots of
(index, weight), zero-weight slots padding short rows. Three layers:

* ``neighbor_lists`` — build the padded lists from dense adjacency
  snapshots (concrete arrays; done once at mixer construction).
* ``gossip_round_reference`` — the single-round oracle: full-gather
  einsum, no chunking. This is what the Pallas kernel and the scan
  fallback are parity-tested against (and it is itself pinned to the
  DenseMixer + DCELMRule round within f32 tolerance).
* ``elm_gossip_scan`` — the jitted off-TPU fallback: ``lax.scan`` over
  rounds, the Laplacian accumulated over neighbor-slot *chunks* so the
  gathered ``(V, chunk, L, M)`` tile — not the full ``(V, d_max, L,
  M)`` gather — bounds peak memory (``elm_gossip_ops`` passes
  ``chunk=GOSSIP_SCAN_CHUNK`` unless its caller sets one).

Payload semantics match the mixers: ``compress="bf16"`` rounds each
element of the gossiped payload to bf16 before the Laplacian is formed
(accumulation stays >= f32), exactly ``mixers.compress_payload``. The
state/output dtype is never widened.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: payload modes the kernel plane understands; richer wire formats
#: (int8/topk/event-triggered) enter through an explicit ``payload=``
#: operand encoded by core/compression.py.
PAYLOAD_MODES = (None, "none", "bf16")


def _check_compress(compress):
    if compress not in PAYLOAD_MODES:
        raise ValueError(
            f"unknown gossip payload mode {compress!r}: the kernel plane "
            f"accepts {PAYLOAD_MODES}; int8/top-k payloads are encoded by "
            "core/compression.py and passed in via payload="
        )
    return None if compress == "none" else compress


def _payload(betas, compress):
    if _check_compress(compress) == "bf16":
        return betas.astype(jnp.bfloat16)
    return betas


def _acc_dtype(payload_dtype):
    """Accumulate the Laplacian at least in f32 (mixers._mix_dtype)."""
    return jnp.promote_types(payload_dtype, jnp.float32)


# ---------------------------------------------------------------------------
# Neighbor-list construction
# ---------------------------------------------------------------------------


def neighbor_lists(adjacencies):
    """Padded CSR-style neighbor lists from dense adjacency snapshots.

    adjacencies: concrete (V, V) or (S, V, V) array (time-varying bases
    keep their leading snapshot axis). Returns ``(idx, w, deg)``:

    * idx: (S, V, d_max) int32 — neighbor indices, short rows padded
      with index 0;
    * w:   (S, V, d_max) — edge weights a_ij, padding slots 0.0 (so a
      padded slot's gathered contribution vanishes — this is also how
      FaultyMixer edge-keep masks fold in: a dropped edge is a
      zero-weight slot in that round's masked snapshot);
    * deg: (S, V) — weighted degrees sum_j a_ij.

    d_max is the max live-neighbor count over all snapshots (>= 1 so
    shapes stay non-empty on edgeless graphs).
    """
    adj = np.asarray(adjacencies)
    if adj.ndim == 2:
        adj = adj[None]
    if adj.ndim != 3 or adj.shape[-1] != adj.shape[-2]:
        raise ValueError(
            f"adjacencies must be (V,V) or (S,V,V), got {adj.shape}"
        )
    S, V, _ = adj.shape
    counts = (adj != 0).sum(axis=-1)
    d_max = max(int(counts.max(initial=0)), 1)
    idx = np.zeros((S, V, d_max), np.int32)
    w = np.zeros((S, V, d_max), adj.dtype)
    for s in range(S):
        for i in range(V):
            nbrs = np.nonzero(adj[s, i])[0]
            idx[s, i, : len(nbrs)] = nbrs
            w[s, i, : len(nbrs)] = adj[s, i, nbrs]
    deg = adj.sum(axis=-1)
    return jnp.asarray(idx), jnp.asarray(w), jnp.asarray(deg)


def _snapshot(arr, k):
    """Round k's slice of a leading-snapshot-axis array (k % S)."""
    S = arr.shape[0]
    if S == 1:
        return arr[0]
    return jnp.take(arr, jnp.mod(k, S), axis=0)


# ---------------------------------------------------------------------------
# Single-round bodies
# ---------------------------------------------------------------------------


def neighbor_laplacian(payload, idx_k, w_k, deg_k, *, chunk=None):
    """lap_i = sum_s w[i,s] payload[idx[i,s]] - deg_i payload_i.

    payload: (V, ...) — any trailing shape; idx_k/w_k: (V, d_max) one
    snapshot; deg_k: (V,). Accumulates in ``_acc_dtype(payload.dtype)``
    over neighbor-slot chunks of size ``chunk`` (default: all slots in
    one gather). Returns the accumulation-dtype Laplacian.
    """
    V, d_max = idx_k.shape
    dt = _acc_dtype(payload.dtype)
    p = payload.astype(dt)
    trail = p.shape[1:]
    pf = p.reshape(V, -1)
    c = d_max if chunk is None else max(1, min(int(chunk), d_max))
    pad = (-d_max) % c
    if pad:
        idx_k = jnp.pad(idx_k, ((0, 0), (0, pad)))
        w_k = jnp.pad(w_k, ((0, 0), (0, pad)))
    steps = (d_max + pad) // c
    wc = w_k.astype(dt)
    lap0 = -deg_k.astype(dt)[:, None] * pf
    if steps == 1:
        g = jnp.take(pf, idx_k, axis=0)  # (V, c, F)
        lap = lap0 + jnp.einsum("vc,vcf->vf", wc, g, precision="highest")
    else:
        ic = idx_k.reshape(V, steps, c).transpose(1, 0, 2)  # (steps, V, c)
        ws = wc.reshape(V, steps, c).transpose(1, 0, 2)

        def acc(lap, sc):
            sl, sw = sc
            g = jnp.take(pf, sl, axis=0)  # (V, c, F)
            return lap + jnp.einsum(
                "vc,vcf->vf", sw, g, precision="highest"
            ), None

        lap, _ = lax.scan(acc, lap0, (ic, ws))
    return lap.reshape((V,) + trail)


def gossip_round_reference(
    betas, omegas, idx_k, w_k, deg_k, scale, *, compress=None
):
    """One eq. (20) round from a padded neighbor list (the oracle).

    betas: (V, L, M) state; omegas: (V, L, L); scale = gamma / (V C).
    Mirrors the DenseMixer + DCELMRule composition: the Laplacian is
    cast back to the state dtype before the Omega contraction, so the
    f32 parity with the dense path is exact up to accumulation order.
    """
    p = _payload(betas, compress)
    lap = neighbor_laplacian(p, idx_k, w_k, deg_k).astype(betas.dtype)
    upd = jnp.einsum("vlk,vkm->vlm", omegas, lap, precision="highest")
    return (betas + scale * upd).astype(betas.dtype)


def gossip_round_payload(
    betas, payload, omegas, idx_k, w_k, deg_k, scale, *, chunk=None
):
    """One round with an explicitly encoded payload (CompressedMixer).

    The Laplacian is formed entirely from ``payload`` (the receivers'
    view of the network — e.g. int8-roundtripped replicas x̂), then the
    update is applied to ``betas``: exactly ``rule(x,
    base.laplacian(x̂, k))`` with the gather/contract pair fused into
    one jitted body.
    """
    lap = neighbor_laplacian(
        payload, idx_k, w_k, deg_k, chunk=chunk
    ).astype(betas.dtype)
    upd = jnp.einsum("vlk,vkm->vlm", omegas, lap, precision="highest")
    return (betas + scale * upd).astype(betas.dtype)


# ---------------------------------------------------------------------------
# Multi-round scan fallback (the off-TPU production path)
# ---------------------------------------------------------------------------


def elm_gossip_scan(
    betas, omegas, idx, w, deg, scale, *, num_rounds, compress=None,
    chunk=None,
):
    """num_rounds fused eq. (20) rounds over the neighbor lists.

    idx/w: (S, V, d_max), deg: (S, V) — round k mixes with snapshot
    k % S (time-varying bases and FaultyMixer masked periods pass their
    whole period here). ``chunk`` bounds the gathered tile at
    (V, chunk, L*M); at ``chunk >= d_max`` the scan body degenerates to
    the single full-gather einsum of the reference oracle.
    """
    _check_compress(compress)

    def round_fn(b, k):
        nxt = gossip_round_reference(
            b, omegas, _snapshot(idx, k), _snapshot(w, k),
            _snapshot(deg, k), scale, compress=compress,
        ) if chunk is None else gossip_round_payload(
            b, _payload(b, compress), omegas, _snapshot(idx, k),
            _snapshot(w, k), _snapshot(deg, k), scale, chunk=chunk,
        )
        return nxt, None

    final, _ = lax.scan(round_fn, betas, jnp.arange(num_rounds))
    return final


# ---------------------------------------------------------------------------
# Dense-round program (the unfused subject + small/complete-graph arm)
# ---------------------------------------------------------------------------


def dense_gossip_rounds(
    betas, omegas, adj, deg, scale, *, num_rounds, compress=None
):
    """num_rounds rounds via the dense (V,V) @ (V, L*M) formulation.

    The exact DenseMixer.laplacian + DCELMRule composition (precomputed
    degrees, payload cast, >= f32 accumulation) as one jittable
    program: the benchmark's unfused subject, and the arm the
    dispatcher lowers to when the graph is too dense for neighbor
    gathers to win (``elm_gossip_ops.prefers_dense``). adj/deg carry a
    leading snapshot axis (S, V, V)/(S, V).
    """
    _check_compress(compress)
    V, L, M = betas.shape

    def round_fn(b, k):
        p = _payload(b.reshape(V, L * M), compress)
        dt = _acc_dtype(p.dtype)
        p = p.astype(dt)
        a_k = _snapshot(adj, k).astype(dt)
        d_k = _snapshot(deg, k).astype(dt)
        a_p = jnp.matmul(a_k, p, precision="highest")
        lap = (a_p - d_k[:, None] * p).astype(b.dtype)
        upd = jnp.einsum(
            "vlk,vkm->vlm", omegas, lap.reshape(V, L, M), precision="highest"
        )
        return (b + scale * upd).astype(b.dtype), None

    final, _ = lax.scan(round_fn, betas, jnp.arange(num_rounds))
    return final
