"""Pluggable communication backends ("mixers") for the ConsensusEngine.

A Mixer answers one question — *how does the network Laplacian term*

    lap_i = sum_{j in N_i} a_ij (x_j - x_i)

*get computed for this execution substrate?* — and one follow-up: *how
do we scan many consensus rounds in that substrate?* Everything about
the update rule itself (DC-ELM's preconditioned step, plain averaging,
D-PSGD parameter mixing) lives in ``core/engine.py`` and is shared by
all mixers.

Two implementations:

* ``DenseMixer`` — all V nodes stacked on the leading axis of every
  leaf, mixing via the dense adjacency (optionally a sequence of
  adjacencies for time-varying topologies). Single-device / vmap path;
  supports arbitrary graphs incl. the paper's random geometric ones.

* ``PpermuteMixer`` — node i is the shard at mesh position i along the
  consensus axes; mixing is neighbor-only ``lax.ppermute`` gossip
  (core/gossip.py) under ``shard_map``. ICI-realizable topologies only.
  This is the production path.

Both accept the inline gossip payload compression knob (``None`` /
``"none"`` / ``"bf16"``): the payload is quantized before the
Laplacian is formed, and the (bounded, gamma-scaled) delta is applied
back in the state dtype. Richer wire formats — int8 with per-tile
scales, top-k sparsification, error feedback, event-triggered
rounds — are ``core/compression.CompressedMixer``, which wraps any
mixer in this file.

``FaultyMixer`` composes over either of the two: it replays a
per-round edge keep-mask stream (``consensus.FaultModel``) so links
drop, burst-fail, or whole nodes crash and rejoin, while the update
rule and execution substrate stay untouched.

Every mixer records exact bytes-on-wire accounting
(``compression.WireStats``) on ``last_wire_stats`` after each ``run``;
the engine surfaces it as ``ConsensusEngine.wire_stats``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import gossip
from repro.core.consensus import Graph
from repro.utils import compat


#: modes the inline ``compress=`` knob understands; richer wire formats
#: (int8 / top-k / event-triggered) live in ``core/compression.py``.
INLINE_COMPRESS_MODES = (None, "none", "bf16")


def _normalize_compress(mode: str | None) -> str | None:
    """Canonicalize the inline knob: ``None`` and ``"none"`` are the
    same (no compression); unknown modes fail at construction time."""
    if mode in (None, "none"):
        return None
    if mode == "bf16":
        return mode
    raise ValueError(
        f"unknown gossip compression {mode!r}: the inline mixer knob "
        f"accepts {INLINE_COMPRESS_MODES}. For int8 / top-k / "
        "event-triggered wire formats build a core.compression."
        "CompressionSpec and wrap the engine with "
        "engine.with_compression(...) (or pass the spec straight to the "
        "engine constructors' compress=)."
    )


def compress_payload(x: jax.Array, mode: str | None) -> jax.Array:
    """Quantize a gossip payload (paper Sec. V: 'reduction of the amount
    of information exchanging')."""
    mode = _normalize_compress(mode)
    if mode is None:
        return x
    return x.astype(jnp.bfloat16)


def _mix_dtype(payload_dtype) -> jnp.dtype:
    """Accumulate the Laplacian at least in f32 (bf16 payloads upcast)."""
    return jnp.promote_types(payload_dtype, jnp.float32)


class DenseMixer:
    """Dense-adjacency mixing over a stacked leading node axis.

    adjacencies: (V, V) for a static graph, or (S, V, V) for a
    time-varying sequence — round k mixes with snapshot k % S.
    """

    def __init__(self, adjacencies, *, compress: str | None = None):
        adjacencies = jnp.asarray(adjacencies)
        if adjacencies.ndim == 2:
            adjacencies = adjacencies[None]
        if adjacencies.ndim != 3 or (
            adjacencies.shape[-1] != adjacencies.shape[-2]
        ):
            raise ValueError(
                f"adjacencies must be (V,V) or (S,V,V), got {adjacencies.shape}"
            )
        self.adjacencies = adjacencies
        # weighted degrees per snapshot, computed once: every scanned
        # round used to redo the (S, V, V) reduction under the trace
        self.degrees = jnp.sum(adjacencies, axis=-1)
        self.compress = _normalize_compress(compress)
        self.last_wire_stats = None

    @classmethod
    def from_graphs(
        cls,
        graphs: Graph | Sequence[Graph],
        *,
        dtype=jnp.float32,
        compress: str | None = None,
    ) -> "DenseMixer":
        if isinstance(graphs, Graph):
            graphs = [graphs]
        adjs = np.stack([np.asarray(g.adjacency) for g in graphs])
        return cls(jnp.asarray(adjs, dtype=dtype), compress=compress)

    @property
    def num_nodes(self) -> int:
        return self.adjacencies.shape[-1]

    def gamma_upper_bound(self) -> float:
        """Paper Thm. 2: 1 / max_k d_max(G_k), joint over snapshots.
        Requires concrete adjacencies (not under a trace)."""
        d_max = float(jnp.max(self.degrees))
        return 1.0 / d_max

    def default_gamma(self, safety: float = 0.9) -> float:
        """safety * gamma_upper_bound() (paper Thm. 2 bound)."""
        return safety * self.gamma_upper_bound()

    def _adjacency(self, k):
        if self.adjacencies.shape[0] == 1:
            return self.adjacencies[0]
        return self.adjacencies[k % self.adjacencies.shape[0]]

    def _degree_row(self, k):
        if self.degrees.shape[0] == 1:
            return self.degrees[0]
        return self.degrees[k % self.degrees.shape[0]]

    def laplacian(self, x, k=0):
        """Stacked Laplacian term, one leaf at a time: A @ x - deg * x."""
        adj = self._adjacency(k)
        deg = self._degree_row(k)

        def leaf(v):
            flat = v.reshape(v.shape[0], -1)
            payload = compress_payload(flat, self.compress)
            dt = _mix_dtype(payload.dtype)
            p = payload.astype(dt)
            a = adj.astype(dt)
            a_p = jnp.matmul(a, p, precision="highest")
            lap = a_p - deg.astype(dt)[:, None] * p
            return lap.astype(v.dtype).reshape(v.shape)

        return jax.tree.map(leaf, x)

    def apply_round(self, rule, x, payload, aux, gamma, k=0):
        """One consensus round where the gossiped payload differs from
        the state — the ``CompressedMixer`` hot path, where ``payload``
        is the receivers' decoded view x̂ of the network while the
        update applies to the true state ``x``. Subclasses may fuse the
        gather + rule into one program; this default is the exact
        composition ``rule(x, laplacian(payload, k), aux, gamma)``.
        """
        return rule(x, self.laplacian(payload, k), aux, gamma)

    def run(
        self,
        rule,
        x,
        aux,
        gamma,
        num_iters: int,
        trace_fn=None,
        state_spec=None,
        aux_spec=None,
    ):
        """Scan ``rule(x, laplacian(x, k), aux, gamma)`` for num_iters rounds."""
        del state_spec, aux_spec  # placement hints are a sharded concern

        def f(carry, k):
            nxt = rule(carry, self.laplacian(carry, k), aux, gamma)
            out = trace_fn(nxt) if trace_fn is not None else jnp.zeros(())
            return nxt, out

        final, traces = lax.scan(f, x, jnp.arange(num_iters))
        self._record_wire(x, num_iters)
        return final, (traces if trace_fn is not None else None)

    def _record_wire(self, x, num_iters: int) -> None:
        """Exact bytes-on-wire: every live directed edge moves one
        payload per round (shape-only — safe under tracing)."""
        from repro.core import compression

        compression.record_wire_stats(self, compression.compute_wire_stats(
            self.compress, compression.dense_out_degrees(self.adjacencies),
            x, self.num_nodes, num_iters,
        ))


class NeighborMixer(DenseMixer):
    """Neighbor-sparse mixing through the fused gossip kernel plane.

    Semantically a ``DenseMixer`` (same constructor, same Laplacian,
    same wire accounting — everything composes: ``FaultyMixer``,
    ``CompressedMixer``, elastic membership), but the adjacency is
    additionally lowered at construction to padded CSR-style neighbor
    lists (``kernels/elm_gossip_ref.neighbor_lists``) and the hot paths
    dispatch to ``kernels/elm_gossip_ops``:

    * ``run`` with a ``DCELMRule`` over stacked f32 betas executes the
      whole round loop as the fused gossip kernel (Pallas on TPU, a
      jitted neighbor-list scan elsewhere) — the dense ``(V, V) @
      (V, L*M)`` matmul and its HBM-round-tripped Laplacian never
      materialize.
    * ``apply_round`` (the CompressedMixer hot path) fuses the
      payload-gather + Omega contraction of one round.
    * ``laplacian`` gathers over neighbor slots instead of the dense
      matmul whenever the graph is genuinely sparse (2 d_max < V).

    On graphs too dense for gathers to win (complete-ish topologies,
    or small V relative to L — ``elm_gossip_ops.prefers_dense``) every
    path falls back to the exact DenseMixer program, so selecting this
    mixer is always safe; parity with ``DenseMixer`` is pinned to f32
    tolerance in tests/test_gossip_kernel.py.
    """

    def __init__(self, adjacencies, *, compress: str | None = None):
        super().__init__(adjacencies, compress=compress)
        from repro.kernels import elm_gossip_ref

        idx, w, _ = elm_gossip_ref.neighbor_lists(self.adjacencies)
        self.neighbor_idx = idx
        self.neighbor_w = w
        self.d_max = int(idx.shape[-1])

    def _lists_row(self, k):
        if self.adjacencies.shape[0] == 1:
            return self.neighbor_idx[0], self.neighbor_w[0], self.degrees[0]
        S = self.adjacencies.shape[0]
        return (
            self.neighbor_idx[k % S],
            self.neighbor_w[k % S],
            self.degrees[k % S],
        )

    def laplacian(self, x, k=0):
        from repro.kernels import elm_gossip_ops, elm_gossip_ref

        if elm_gossip_ops.laplacian_prefers_dense(
            self.num_nodes, self.d_max
        ):
            return super().laplacian(x, k)
        idx_k, w_k, deg_k = self._lists_row(k)

        def leaf(v):
            flat = v.reshape(v.shape[0], -1)
            payload = compress_payload(flat, self.compress)
            lap = elm_gossip_ref.neighbor_laplacian(
                payload, idx_k, w_k, deg_k
            )
            return lap.astype(v.dtype).reshape(v.shape)

        return jax.tree.map(leaf, x)

    def _fused_ok(self, rule, x, aux, gamma, *, allow_bf16: bool) -> bool:
        """The fused kernel covers exactly the DC-ELM hot path: stacked
        f32 (V, L, M) betas, (V, L, L) Omegas, a concrete-or-traced
        gamma, inline payload mode None/bf16, on a graph sparse enough
        for the gather formulation to win."""
        from repro.core.engine import DCELMRule
        from repro.kernels import elm_gossip_ops

        if not isinstance(rule, DCELMRule) or gamma is None:
            return False
        if self.compress is not None and not allow_bf16:
            return False
        if not (
            isinstance(x, jax.Array)
            and x.ndim == 3
            and x.dtype == jnp.float32
        ):
            return False
        V, L, M = x.shape
        if V != self.num_nodes:
            return False
        if not (
            isinstance(aux, jax.Array)
            and aux.shape == (V, L, L)
            and aux.dtype == jnp.float32
        ):
            return False
        return not elm_gossip_ops.prefers_dense(V, self.d_max, L, M)

    def _scale(self, rule, gamma):
        return gamma / (rule.num_nodes * rule.C)

    def apply_round(self, rule, x, payload, aux, gamma, k=0):
        fusable = (
            self.compress is None
            and isinstance(payload, jax.Array)
            and payload.ndim == 3
            and payload.dtype == jnp.float32
            and self._fused_ok(rule, x, aux, gamma, allow_bf16=False)
        )
        if not fusable:
            return super().apply_round(rule, x, payload, aux, gamma, k)
        from repro.kernels import elm_gossip_ops

        idx_k, w_k, deg_k = self._lists_row(k)
        return elm_gossip_ops.fused_gossip_round(
            x, payload, aux, idx_k, w_k, deg_k, self._scale(rule, gamma)
        )

    def run(
        self,
        rule,
        x,
        aux,
        gamma,
        num_iters: int,
        trace_fn=None,
        state_spec=None,
        aux_spec=None,
    ):
        if (
            trace_fn is not None
            or num_iters <= 0
            or not self._fused_ok(rule, x, aux, gamma, allow_bf16=True)
        ):
            return super().run(
                rule, x, aux, gamma, num_iters, trace_fn, state_spec,
                aux_spec,
            )
        from repro.kernels import elm_gossip_ops

        final = elm_gossip_ops.fused_gossip_rounds(
            x, aux, self.neighbor_idx, self.neighbor_w, self.degrees,
            self._scale(rule, gamma), num_rounds=num_iters,
            compress=self.compress,
        )
        self._record_wire(x, num_iters)
        return final, None


@dataclasses.dataclass(frozen=True)
class PpermuteMixer:
    """ppermute-gossip mixing for ICI topologies (gossip.GossipSpec).

    ``laplacian`` is usable inside any caller-managed ``shard_map``
    (that is how distributed/steps.py mixes model-sharded replicas);
    ``run`` additionally owns the shard_map + scan wrapping for the
    standard layout where state leaves carry a leading node axis of
    size V = prod(consensus axes), sharded across those axes.
    """

    spec: gossip.GossipSpec
    axis_sizes: dict
    mesh: jax.sharding.Mesh | None = None
    compress: str | None = None
    # jitted shard_map(scan) programs keyed by (rule, num_iters, specs,
    # has_aux) — reusing the engine across calls (the streaming loop
    # pattern) then hits the compile cache instead of retracing.
    _programs: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )
    def __post_init__(self):
        # wire accounting is mutable state on a frozen dataclass; it is
        # written through compression.record_wire_stats
        object.__setattr__(self, "compress", _normalize_compress(self.compress))
        object.__setattr__(self, "last_wire_stats", None)

    def _record_wire(self, x, num_iters: int) -> None:
        from repro.core import compression

        deg = self.spec.degree(self.axis_sizes)
        compression.record_wire_stats(self, compression.compute_wire_stats(
            self.compress,
            np.full((1, self.num_nodes), deg, dtype=np.int64),
            x, self.num_nodes, num_iters,
        ))

    @classmethod
    def for_mesh(
        cls,
        mesh: jax.sharding.Mesh,
        spec: gossip.GossipSpec,
        *,
        compress: str | None = None,
    ) -> "PpermuteMixer":
        gossip.validate_spec(spec, mesh)
        return cls(
            spec=spec,
            axis_sizes=gossip.mesh_axis_sizes(mesh),
            mesh=mesh,
            compress=compress,
        )

    @property
    def num_nodes(self) -> int:
        return self.spec.num_nodes(self.axis_sizes)

    def gamma_upper_bound(self) -> float:
        return self.spec.gamma_upper_bound(self.axis_sizes)

    def default_gamma(self, safety: float = 0.9) -> float:
        return safety * self.gamma_upper_bound()

    def node_pspec(self) -> P:
        """PartitionSpec placing the leading node axis on the consensus axes."""
        axes = self.spec.axes
        return P(axes if len(axes) > 1 else axes[0])

    def laplacian(self, x, k=0):
        """Neighbor Laplacian via ppermute — call inside shard_map."""
        del k  # ICI topologies are static; snapshots don't vary per round
        if self.compress is not None:
            payload = jax.tree.map(
                lambda v: compress_payload(v, self.compress), x
            )
        else:
            payload = x
        lap = gossip.neighbor_laplacian(payload, self.spec, self.axis_sizes)
        return jax.tree.map(lambda v, d: d.astype(v.dtype), x, lap)

    def run(
        self,
        rule,
        x,
        aux,
        gamma,
        num_iters: int,
        trace_fn=None,
        state_spec=None,
        aux_spec=None,
    ):
        """shard_map(scan(rule ∘ laplacian)) on the mesh: one collective
        program for the whole consensus run, neighbor-only ICI traffic
        inside. Programs are cached per (rule, num_iters, specs) and
        take gamma as a traced argument, so repeated calls on the same
        mixer — e.g. every streaming chunk event — compile once.
        """
        if trace_fn is not None:
            raise NotImplementedError(
                "per-round traces are a simulated-path (DenseMixer) feature"
            )
        if self.mesh is None:
            raise ValueError(
                "PpermuteMixer.run needs a mesh; build via for_mesh(...)"
            )
        sspec = self.node_pspec() if state_spec is None else state_spec
        aspec = self.node_pspec() if aux_spec is None else aux_spec
        key = (rule, num_iters, sspec, aspec, aux is None)
        fn = self._programs.get(key)
        if fn is None:
            if aux is None:

                def scanned(b, g):
                    def f(carry, k):
                        return rule(carry, self.laplacian(carry, k), None, g), None

                    final, _ = lax.scan(f, b, jnp.arange(num_iters))
                    return final

                fn = jax.jit(compat.shard_map(
                    scanned, self.mesh, in_specs=(sspec, P()), out_specs=sspec
                ))
            else:

                def scanned(b, o, g):
                    def f(carry, k):
                        return rule(carry, self.laplacian(carry, k), o, g), None

                    final, _ = lax.scan(f, b, jnp.arange(num_iters))
                    return final

                fn = jax.jit(compat.shard_map(
                    scanned, self.mesh,
                    in_specs=(sspec, aspec, P()), out_specs=sspec,
                ))
            self._programs[key] = fn
        gamma = jnp.asarray(gamma)
        self._record_wire(x, num_iters)
        if aux is None:
            return fn(x, gamma), None
        return fn(x, aux, gamma), None


class FaultyMixer:
    """Fault-injection wrapper: a base mixer plus per-round edge masks.

    ``edge_keep`` is an (R, V, V) symmetric 0/1 stream (typically
    ``consensus.FaultModel.edge_keep``); round k mixes with mask
    k % R. Composition per base:

    * ``DenseMixer`` — each round's dense adjacency is multiplied by
      its mask; time-varying bases compose (snapshot k % S, mask
      k % R) over one period of length lcm(S, R).

    * ``PpermuteMixer`` — the masks are folded onto the ppermute
      schedule (``gossip.fold_edge_keep``) and each permutation's
      received contribution is weighted inside the shard_map body, so
      a dropped link contributes zero to the Laplacian while the
      collective schedule — and therefore the compiled
      ``shard_map(scan)`` program — is byte-identical to the
      fault-free one. The folded masks enter the jitted program as a
      *traced* argument and programs are cached on the shared base
      mixer, so sweeping failure rates (new masks, same shapes) never
      recompiles.

    Fault masks only remove edges, so the base mixer's Thm. 2 step
    bound (``default_gamma``) remains valid for every masked snapshot.
    """

    def __init__(self, base, edge_keep):
        edge_keep = np.asarray(edge_keep, dtype=np.float32)
        if edge_keep.ndim == 2:
            edge_keep = edge_keep[None]
        V = base.num_nodes
        if edge_keep.ndim != 3 or edge_keep.shape[-2:] != (V, V):
            raise ValueError(
                f"edge_keep must be (R, {V}, {V}), got {edge_keep.shape}"
            )
        if not np.allclose(edge_keep, np.transpose(edge_keep, (0, 2, 1))):
            raise ValueError("edge_keep must be symmetric per round")
        self.base = base
        self.edge_keep = edge_keep
        self.num_rounds = edge_keep.shape[0]
        self.last_wire_stats = None
        if isinstance(base, DenseMixer):
            S = base.adjacencies.shape[0]
            R = edge_keep.shape[0]
            period = math.lcm(S, R)
            masked = (
                np.asarray(base.adjacencies)[np.arange(period) % S]
                * edge_keep[np.arange(period) % R]
            )
            # type(base), not DenseMixer: a NeighborMixer base rebuilds
            # its padded neighbor lists from the masked period, folding
            # each round's edge-keep mask into per-neighbor-slot weights
            # (a dropped edge is a zero-weight slot), so the fused
            # kernel path survives fault injection
            self._dense = type(base)(
                jnp.asarray(masked, base.adjacencies.dtype),
                compress=base.compress,
            )
            self._keep = None
        elif isinstance(base, PpermuteMixer):
            self._dense = None
            self._keep = jnp.asarray(
                gossip.fold_edge_keep(base.spec, base.axis_sizes, edge_keep)
            )
        else:
            raise TypeError(
                f"FaultyMixer wraps DenseMixer or PpermuteMixer, got "
                f"{type(base).__name__}"
            )

    @classmethod
    def from_fault_model(cls, base, model, num_rounds: int) -> "FaultyMixer":
        """Wrap ``base`` with ``model``'s fault trace over num_rounds."""
        return cls(base, model.edge_keep(num_rounds))

    @property
    def num_nodes(self) -> int:
        return self.base.num_nodes

    @property
    def compress(self):
        return self.base.compress

    def gamma_upper_bound(self) -> float:
        """Faults only remove edges, so the base bound stays valid."""
        return self.base.gamma_upper_bound()

    def default_gamma(self, safety: float = 0.9) -> float:
        return self.base.default_gamma(safety)

    def node_pspec(self) -> P:
        return self.base.node_pspec()

    def laplacian(self, x, k=0):
        """Masked Laplacian for round k (k % R into the fault trace).

        Dense: directly callable. Ppermute: call inside a
        caller-managed shard_map over the base mesh — the shard finds
        its own mask row via its mesh position.
        """
        if self._dense is not None:
            return self._dense.laplacian(x, k)
        base = self.base
        my = gossip.global_node_index(base.spec, base.axis_sizes)
        keep = self._keep[jnp.mod(jnp.asarray(k), self.num_rounds), :, my]
        return self._masked_laplacian(x, keep)

    def apply_round(self, rule, x, payload, aux, gamma, k=0):
        """Masked round with an explicit payload — delegates to the
        masked-period inner mixer (dense bases only; the ppermute arm
        has no payload-splitting caller)."""
        if self._dense is None:
            raise NotImplementedError(
                "apply_round with an explicit payload is a dense-base "
                "feature"
            )
        return self._dense.apply_round(rule, x, payload, aux, gamma, k)

    def _masked_laplacian(self, x, keep):
        base = self.base
        if base.compress is not None:
            payload = jax.tree.map(
                lambda v: compress_payload(v, base.compress), x
            )
        else:
            payload = x
        lap = gossip.masked_neighbor_laplacian(
            payload, base.spec, base.axis_sizes, keep
        )
        return jax.tree.map(lambda v, d: d.astype(v.dtype), x, lap)

    def run(
        self,
        rule,
        x,
        aux,
        gamma,
        num_iters: int,
        trace_fn=None,
        state_spec=None,
        aux_spec=None,
    ):
        if self._dense is not None:
            out = self._dense.run(
                rule, x, aux, gamma, num_iters, trace_fn, state_spec,
                aux_spec,
            )
            # the masked-adjacency inner mixer counted only live links
            from repro.core import compression

            compression.record_wire_stats(
                self, self._dense.last_wire_stats
            )
            return out
        base = self.base
        if trace_fn is not None:
            raise NotImplementedError(
                "per-round traces are a simulated-path (DenseMixer) feature"
            )
        if base.mesh is None:
            raise ValueError(
                "FaultyMixer.run over ppermute needs a mesh; build the "
                "base via PpermuteMixer.for_mesh(...)"
            )
        sspec = self.node_pspec() if state_spec is None else state_spec
        aspec = self.node_pspec() if aux_spec is None else aux_spec
        # cache on the *base* mixer: the folded masks are a traced
        # input, so every FaultyMixer sharing this base (e.g. a
        # failure-rate sweep) reuses one compiled program per
        # (rule, num_iters, specs, mask period).
        key = (
            "faulty", rule, num_iters, sspec, aspec, aux is None,
            self._keep.shape,
        )
        fn = base._programs.get(key)
        if fn is None:
            R = self.num_rounds

            def scanned(b, o, keep_all, g):
                my = gossip.global_node_index(base.spec, base.axis_sizes)

                def f(carry, k):
                    keep = keep_all[jnp.mod(k, R), :, my]
                    lap = self._masked_laplacian(carry, keep)
                    return rule(carry, lap, o, g), None

                final, _ = lax.scan(f, b, jnp.arange(num_iters))
                return final

            if aux is None:
                fn = jax.jit(compat.shard_map(
                    lambda b, keep_all, g: scanned(b, None, keep_all, g),
                    base.mesh,
                    in_specs=(sspec, P(), P()),
                    out_specs=sspec,
                ))
            else:
                fn = jax.jit(compat.shard_map(
                    scanned,
                    base.mesh,
                    in_specs=(sspec, aspec, P(), P()),
                    out_specs=sspec,
                ))
            base._programs[key] = fn
        gamma = jnp.asarray(gamma)
        self._record_wire(x, num_iters)
        if aux is None:
            return fn(x, self._keep, gamma), None
        return fn(x, aux, self._keep, gamma), None

    def _record_wire(self, x, num_iters: int) -> None:
        """Exact live-link accounting over the folded ppermute masks:
        in-degree == out-degree per node because the edge masks are
        symmetric and the perm schedule covers both directions."""
        from repro.core import compression

        out_deg = (np.asarray(self._keep) != 0).sum(axis=1).astype(np.int64)
        compression.record_wire_stats(self, compression.compute_wire_stats(
            self.compress, out_deg, x, self.num_nodes, num_iters,
        ))
