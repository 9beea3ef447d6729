"""Fused consensus-round Pallas kernel over padded neighbor lists.

One grid pass applies the paper's eq. (20) update

    beta_i += scale * Omega_i @ (sum_s w[i,s] beta[idx[i,s]] - deg_i beta_i)

for a block of ``block_v`` nodes per program: the neighbor beta tiles
are gathered from a VMEM-resident copy of the full state, the Laplacian
is accumulated in VMEM registers (f32), and the Omega contraction +
state update write straight to the output block — the ``(V, L, M)``
Laplacian never exists in HBM. Layout inside the kernel is
``(V, M, L)``: L (128-aligned) rides the lane dimension so the state
stays physically compact for small M (the (V, L, M) layout would pad
M to a full 128-lane tile and blow the VMEM budget ~16x at M=8).

Arms:

* ``elm_gossip_pallas`` — ``num_rounds`` rounds as an outer
  ``lax.scan`` over per-round kernel launches (the state round-trips
  HBM between rounds; the Laplacian still never does). bf16 payload
  (``compress="bf16"``) casts the gathered/self payload in-kernel and
  accumulates in f32, matching ``mixers.compress_payload``. An
  explicitly encoded ``payload=`` operand (int8-roundtripped replicas
  from core/compression.py) is gathered instead of the state —
  the fused CompressedMixer round (single-round only: the payload is
  re-encoded outside per round).
* ``elm_gossip_pallas_multiround`` — the small-state arm: the whole
  state, Omegas and every topology snapshot stay resident in VMEM and
  an in-kernel ``lax.fori_loop`` runs all rounds back-to-back, so the
  state skips its per-round HBM round-trips too. Gate on
  ``multiround_vmem_bytes`` (see elm_gossip_ops).

Off TPU both arms run under ``interpret=True`` for correctness tests;
the production CPU path is ``elm_gossip_ref.elm_gossip_scan``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vmem import vmem_params


def _rup(x: int, m: int) -> int:
    return x + (-x) % m


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array, scalar reads


def _node_update(
    src_ref, beta_row, omega_row, idx_ref, w_ref, deg_ref, scale, *,
    node, row, d_max, bf16,
):
    """beta_i + scale * (sum_s w[i,s] src[idx[i,s]] - deg_i src_i) @ Omega_i^T.

    ``node`` indexes the gather source and ``row`` the flattened
    (snapshot, node) neighbor-list row. The gathered (Mp, Lp) tiles are
    ref-level reads at a scalar index from SMEM; the f32 Laplacian never
    leaves VMEM. upd[m, l] = sum_k lap[m, k] * Omega[l, k] contracts
    both lane dims on the MXU.
    """

    def payload(j):
        p = src_ref[j]
        if bf16:
            p = p.astype(jnp.bfloat16)
        return p.astype(jnp.float32)

    def acc(s, lap):
        slot = row * d_max + s
        return lap + w_ref[slot] * payload(idx_ref[slot])

    lap = jax.lax.fori_loop(0, d_max, acc, -deg_ref[row] * payload(node))
    upd = jax.lax.dot_general(
        lap, omega_row,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # f32 state and Omega
        preferred_element_type=jnp.float32,
    )
    return beta_row + scale * upd


def _round_kernel(
    idx_ref, w_ref, deg_ref, scale_ref, src_ref, beta_ref, omega_ref,
    out_ref, *, block_v, d_max, bf16,
):
    """One round for a block of ``block_v`` nodes. ``src_ref`` is the
    whole gather source (state or encoded payload) resident in VMEM;
    ``beta_ref`` is this block's state rows."""
    base = pl.program_id(0) * block_v

    def node(v, carry):
        out_ref[v] = _node_update(
            src_ref, beta_ref[v], omega_ref[v], idx_ref, w_ref, deg_ref,
            scale_ref[0], node=base + v, row=base + v, d_max=d_max,
            bf16=bf16,
        )
        return carry

    jax.lax.fori_loop(0, block_v, node, 0)


def _multiround_kernel(
    idx_ref, w_ref, deg_ref, scale_ref, beta_ref, omega_ref, out_ref,
    next_ref, *, num_nodes, d_max, num_snapshots, num_rounds, bf16,
):
    """All rounds with the state resident: round k reads ``out_ref``,
    writes every node's update to ``next_ref``, then copies it back
    (every node must see the same round-k state)."""
    out_ref[...] = beta_ref[...]

    def round_fn(k, carry):
        first_row = jax.lax.rem(k, num_snapshots) * num_nodes

        def node(v, c):
            next_ref[v] = _node_update(
                out_ref, out_ref[v], omega_ref[v], idx_ref, w_ref,
                deg_ref, scale_ref[0], node=v, row=first_row + v,
                d_max=d_max, bf16=bf16,
            )
            return c

        jax.lax.fori_loop(0, num_nodes, node, 0)
        out_ref[...] = next_ref[...]
        return carry

    jax.lax.fori_loop(0, num_rounds, round_fn, 0)


# ---------------------------------------------------------------------------
# Padding / layout
# ---------------------------------------------------------------------------


def _prep(betas, omegas, idx, w, deg, block_v):
    """(V, L, M) -> padded kernel operands in the (V, M, L) layout.

    The neighbor lists go to SMEM flattened per snapshot: idx/w become
    (S, Vp * d_max) and deg (S, Vp), so row ``v`` of a snapshot starts
    at ``v * d_max``.
    """
    V, L, M = betas.shape
    S, _, d_max = idx.shape
    bv = max(1, min(int(block_v), V))
    Vp = _rup(V, bv)
    Lp = _rup(L, 128)
    Mp = _rup(M, 8)
    bt = jnp.transpose(betas, (0, 2, 1)).astype(jnp.float32)
    bt = jnp.pad(bt, ((0, Vp - V), (0, Mp - M), (0, Lp - L)))
    om = jnp.pad(
        omegas.astype(jnp.float32),
        ((0, Vp - V), (0, Lp - L), (0, Lp - L)),
    )
    pad_v = ((0, 0), (0, Vp - V), (0, 0))
    ip = jnp.pad(idx.astype(jnp.int32), pad_v).reshape(S, Vp * d_max)
    wp = jnp.pad(w.astype(jnp.float32), pad_v).reshape(S, Vp * d_max)
    dg = jnp.pad(deg.astype(jnp.float32), ((0, 0), (0, Vp - V)))
    return bt, om, ip, wp, dg, (Vp, Lp, Mp, bv)


def _unpack(out, V, L, M, dtype):
    return jnp.transpose(out[:V, :M, :L], (0, 2, 1)).astype(dtype)


def _snapshot(arr, k):
    S = arr.shape[0]
    return arr[0] if S == 1 else jnp.take(arr, jnp.mod(k, S), axis=0)


def round_vmem_bytes(V, L, M, block_v, *, payload=False) -> int:
    """VMEM the per-round arm keeps: the whole gather source (single
    buffered — its block never moves), and double-buffered per-block
    state, Omega and output tiles. ``vmem_params`` requests this plus
    a margin; ``fit_block_v`` holds it to ``vmem_budget()``."""
    Vp, Lp, Mp = _rup(V, block_v), _rup(L, 128), _rup(M, 8)
    state = 4 * Vp * Mp * Lp
    tiles = 2 * 4 * block_v * (2 * Mp * Lp + Lp * Lp)
    return state * (2 if payload else 1) + tiles


def fit_block_v(V, L, M, block_v, budget, *, payload=False) -> int:
    """The largest node block <= ``block_v`` whose resident set fits
    ``budget`` (1 when none does: the Omega tile is then the floor).
    The dispatchers pass ``vmem_budget()``, the VMEM the kernel may
    request, not the 16 MiB default scoped limit: at V = 1024, L = 256
    the gather source alone fills that, and the block would fall to 1."""
    bv = max(1, min(int(block_v), V))
    while bv > 1 and round_vmem_bytes(V, L, M, bv, payload=payload) > budget:
        bv //= 2
    return bv


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def elm_gossip_pallas(
    betas, omegas, idx, w, deg, scale, *, num_rounds=1, block_v=8,
    compress=None, payload=None, interpret=False,
):
    """num_rounds fused eq. (20) rounds, one kernel launch per round.

    betas: (V, L, M); omegas: (V, L, L); idx/w: (S, V, d_max);
    deg: (S, V); scale = gamma / (VC) (scalar, may be traced).
    compress="bf16" casts the gossiped payload in-kernel;
    payload=(V, L, M) gathers an explicitly encoded payload instead
    (single round only — the encoder reruns between rounds).
    """
    if payload is not None and num_rounds != 1:
        raise ValueError(
            "an explicit payload= is re-encoded outside the kernel every "
            f"round, so it implies num_rounds=1 (got {num_rounds})"
        )
    V, L, M = betas.shape
    d_max = idx.shape[-1]
    bt, om, ip, wp, dg, (Vp, Lp, Mp, bv) = _prep(
        betas, omegas, idx, w, deg, block_v
    )
    scale = jnp.asarray(scale, jnp.float32).reshape(1)
    kernel = functools.partial(
        _round_kernel, block_v=bv, d_max=d_max, bf16=compress == "bf16"
    )
    call = pl.pallas_call(
        kernel,
        grid=(Vp // bv,),
        in_specs=[
            _SMEM, _SMEM, _SMEM, _SMEM,
            pl.BlockSpec(
                (Vp, Mp, Lp), lambda i: (0, 0, 0),
                pipeline_mode=pl.Buffered(1),
            ),
            pl.BlockSpec((bv, Mp, Lp), lambda i: (i, 0, 0)),
            pl.BlockSpec((bv, Lp, Lp), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bv, Mp, Lp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Vp, Mp, Lp), jnp.float32),
        compiler_params=vmem_params(
            round_vmem_bytes(V, L, M, bv, payload=payload is not None)
        ),
        interpret=interpret,
        name="elm_gossip_pallas",
    )

    if payload is None:

        def one_round(b, k):
            lists = (_snapshot(ip, k), _snapshot(wp, k), _snapshot(dg, k))
            return call(*lists, scale, b, b, om), None

        if num_rounds == 1:
            out = one_round(bt, 0)[0]
        else:
            out, _ = jax.lax.scan(one_round, bt, jnp.arange(num_rounds))
        return _unpack(out, V, L, M, betas.dtype)

    pt = jnp.transpose(payload, (0, 2, 1)).astype(jnp.float32)
    pt = jnp.pad(pt, ((0, Vp - V), (0, Mp - M), (0, Lp - L)))
    out = call(ip[0], wp[0], dg[0], scale, pt, bt, om)
    return _unpack(out, V, L, M, betas.dtype)


def elm_gossip_pallas_multiround(
    betas, omegas, idx, w, deg, scale, *, num_rounds, compress=None,
    interpret=False,
):
    """All rounds in one kernel: state resident in VMEM throughout.

    Small-state arm — gate callers on ``multiround_vmem_bytes``. The
    topology snapshots (time-varying bases, FaultyMixer masked periods)
    ride along in SMEM and round k picks snapshot k % S in-kernel.
    """
    V, L, M = betas.shape
    S, _, d_max = idx.shape
    bt, om, ip, wp, dg, (Vp, Lp, Mp, _) = _prep(
        betas, omegas, idx, w, deg, block_v=V
    )
    scale = jnp.asarray(scale, jnp.float32).reshape(1)

    def whole(*dims):
        return pl.BlockSpec(dims, lambda: (0,) * len(dims))

    kernel = functools.partial(
        _multiround_kernel, num_nodes=Vp, d_max=d_max, num_snapshots=S,
        num_rounds=num_rounds, bf16=compress == "bf16",
    )
    out = pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[
            _SMEM, _SMEM, _SMEM, _SMEM,
            whole(Vp, Mp, Lp), whole(Vp, Lp, Lp),
        ],
        out_specs=whole(Vp, Mp, Lp),
        out_shape=jax.ShapeDtypeStruct((Vp, Mp, Lp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Vp, Mp, Lp), jnp.float32)],
        compiler_params=vmem_params(multiround_vmem_bytes(V, L, M, S, d_max)),
        interpret=interpret,
        name="elm_gossip_pallas_multiround",
    )(ip.reshape(-1), wp.reshape(-1), dg.reshape(-1), scale, bt, om)
    return _unpack(out, V, L, M, betas.dtype)


def multiround_vmem_bytes(V, L, M, S, d_max) -> int:
    """Resident VMEM bytes of the multi-round arm: state in, out and
    next, plus every Omega (the neighbor lists live in SMEM)."""
    del S, d_max
    Lp, Mp = _rup(L, 128), _rup(M, 8)
    return 3 * 4 * V * Mp * Lp + 4 * V * Lp * Lp
