"""ConsensusEngine — the one consensus update rule, every execution path.

The paper's fusion-center-free iteration (Algorithm 1, eq. 20)

    beta_i(k+1) = beta_i(k) + (gamma / VC) * Omega_i * lap_i,
    lap_i = sum_{j in N_i} a_ij (beta_j(k) - beta_i(k))

used to live in four hand-rolled copies (simulated step/run, the two
sharded bodies, plus per-consumer glue). It now lives **here, once**,
factored as

    Engine  =  Mixer (who computes lap_i, and where)   [core/mixers.py]
            x  UpdateRule (what lap_i does to the state)     [this file]

UpdateRules:
  * ``DCELMRule``   — the paper's preconditioned step (Omega_i metric).
  * ``AverageRule`` — identity metric: plain consensus averaging
    (gossip.neighbor_avg semantics) and D-PSGD parameter mixing
    (core/dsgd.py) over arbitrary pytrees.

On top of the round driver, ``stream_chunk`` implements **Algorithm 2**
end-to-end — Woodbury remove/add of a data chunk, beta re-seed at the
new local optimum, K consensus rounds — and runs on *both* mixers, so
the sharded production path gets online learning from the same code
the simulated fidelity path is tested with. Streaming also survives
churn: ``stream_leave``/``stream_join`` remove or add whole nodes
(their data shard included) with a rank-L Woodbury re-target of every
survivor's preconditioner, and ``with_faults`` wraps any engine's
mixer in a fault-injection layer (``mixers.FaultyMixer``).
``with_compression`` (or a ``compression.CompressionSpec`` handed to
any constructor's ``compress=``) wraps the mixer in a
``CompressedMixer`` — quantized/sparsified wire payloads with error
feedback and event-triggered rounds — and every run surfaces exact
bytes-on-wire accounting as ``ConsensusEngine.wire_stats``. See
DESIGN.md §4, §8 and §9.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gossip, online
from repro.core.compression import CompressedMixer, CompressionSpec
from repro.core.consensus import FaultModel, Graph
from repro.core.mixers import (
    DenseMixer,
    FaultyMixer,
    NeighborMixer,
    PpermuteMixer,
)
from repro.core.scopes import phase
from repro.utils import compat


# ---------------------------------------------------------------------------
# Update rules
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DCELMRule:
    """Paper eq. (20): beta += (gamma/VC) * Omega @ lap.

    ``aux`` carries the stacked frozen preconditioners Omega_i with the
    same leading node axis as the state ((V, L, L) dense, (1, L, L) per
    shard) — the einsum below is identical in both layouts. This is the
    only implementation of the DC-ELM round body in the codebase.
    """

    num_nodes: int
    C: float

    def __call__(self, x, lap, aux, gamma):
        V, C = self.num_nodes, self.C
        update = jnp.einsum("vlk,vkm->vlm", aux, lap, precision="highest")
        return x + (gamma / (V * C)) * update


@dataclasses.dataclass(frozen=True)
class AverageRule:
    """Identity-metric mixing x += gamma * lap, per pytree leaf.

    The paper's rule with Omega_i = I: plain consensus averaging, and —
    applied to parameter pytrees after a local optimizer step — the
    D-PSGD mixing used by the deep-net trainer (core/dsgd.py), where the
    non-quadratic objective has no closed-form ELM preconditioner.
    """

    def __call__(self, x, lap, aux, gamma):
        del aux
        return jax.tree.map(lambda v, d: v + gamma * d.astype(v.dtype), x, lap)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ConsensusEngine:
    """One consensus iteration = rule(state, mixer.laplacian(state)).

    Wire-format and robustness knobs compose around the mixer without
    touching the rule:

    * ``compress=`` on the convenience constructors — ``None``/"none"
      (no compression, the default) or "bf16" select the mixers' inline
      payload cast; an "int8"/"topk" mode string or a full
      ``compression.CompressionSpec`` (error feedback, event-triggered
      broadcasts) wraps the mixer in a ``CompressedMixer``.
      ``with_compression(eng, spec)`` does the same to an existing
      engine.
    * ``with_faults(eng, model_or_masks)`` injects a per-round edge
      keep-mask stream (``mixers.FaultyMixer``). The two stack —
      compression always sits outermost, so encoded payloads cross
      whatever links the fault trace left alive.

    After any ``run``/``stream_chunk``, ``eng.wire_stats`` holds the
    exact bytes-on-wire accounting of the rounds just executed
    (``compression.WireStats``), on every mixer stack.

    ``secure`` carries a ``secure.SecureAggregationSpec`` (set via
    ``with_secure_aggregation``) that the vertical plane
    (``core/vertical.py``) picks up for its sum-reductions — pairwise
    additive masks on the assembly payloads. It deliberately does NOT
    mask the per-round Laplacian gossip: lap_i is a *neighborhood*
    difference, not a network-wide sum, so pairwise masks would not
    cancel there; secure aggregation scopes to genuine sum-reductions.
    """

    mixer: Any
    rule: Callable
    secure: Any = None

    @property
    def wire_stats(self):
        """Exact ``compression.WireStats`` of the last run (or None)."""
        return getattr(self.mixer, "last_wire_stats", None)

    def gamma_upper_bound(self) -> float | None:
        """Thm. 2's 1/d_max for the *active* mixer (None if the mixer
        cannot say, e.g. traced adjacencies).

        Membership churn moves this bound: ``stream_join``'s default
        all-incumbent topology jumps d_max to ~V, so always re-read the
        bound from the engine ``stream_join``/``stream_leave`` return
        rather than reusing the pre-churn value.
        """
        fn = getattr(self.mixer, "gamma_upper_bound", None)
        if fn is None:
            return None
        try:
            return float(fn())
        except (
            TypeError,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
        ):
            return None

    def _validate_gamma(self, gamma, check_gamma: bool) -> None:
        """Reject a concrete gamma outside (0, 1/d_max) of the active
        mixer — the silent-divergence bug after membership churn.

        Traced gammas (inside jit/shard_map) and mixers without a
        concrete bound are skipped; ``check_gamma=False`` is the escape
        hatch for deliberate above-bound experiments (paper Fig. 4(a)).
        """
        if not check_gamma or gamma is None:
            return
        try:
            g = float(gamma)
        except (
            TypeError,
            jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
        ):
            return
        bound = self.gamma_upper_bound()
        if bound is None:
            return
        if not 0.0 < g < bound:
            raise ValueError(
                f"gamma={g:.6g} violates Thm. 2's 0 < gamma < 1/d_max "
                f"= {bound:.6g} for the active mixer (the bound moves "
                "under membership churn — re-read it from the engine "
                "stream_join/stream_leave return). Pass "
                "check_gamma=False to run a deliberate divergence "
                "experiment."
            )

    def step(self, x, aux=None, gamma=None, k=0, *, check_gamma=True):
        """A single consensus round, in the mixer's execution context.

        For ``PpermuteMixer`` this must run inside a caller-managed
        shard_map (distributed/steps.py and core/elm_head.py do this to
        mix replicas whose leaves are further model-sharded); for
        ``DenseMixer`` it is directly callable/jittable. A concrete
        gamma is validated against the active mixer's Thm. 2 bound
        (``check_gamma=False`` opts out).
        """
        self._validate_gamma(gamma, check_gamma)
        return self.rule(x, self.mixer.laplacian(x, k), aux, gamma)

    def run(
        self,
        x,
        aux,
        gamma,
        num_iters: int,
        *,
        trace_fn=None,
        state_spec=None,
        aux_spec=None,
        check_gamma=True,
    ):
        """num_iters rounds under the mixer's scan driver.

        trace_fn: optional per-round metric over the stacked state
        (DenseMixer only). state_spec/aux_spec: PartitionSpec overrides
        for states whose trailing dims are also sharded (PpermuteMixer
        only). A concrete gamma is validated against the active mixer's
        Thm. 2 bound at entry (``check_gamma=False`` opts out for
        deliberate divergence experiments). Returns
        (final_state, traces or None).
        """
        self._validate_gamma(gamma, check_gamma)
        with phase("rounds"):
            return self.mixer.run(
                self.rule, x, aux, gamma, num_iters, trace_fn, state_spec,
                aux_spec,
            )

    # -- streaming (paper Algorithm 2) ------------------------------------

    def stream_init(
        self,
        H_nodes=None,
        T_nodes=None,
        *,
        X_nodes=None,
        feature_map=None,
    ) -> "StreamState":
        """Per-node sufficient statistics + local ridge seed.

        Two entry shapes, both through the statistics plane
        (`core/stats.py`, Cholesky Omega):

        * materialized features: ``stream_init(H_nodes, T_nodes)`` with
          H:(V,Ni,L), T:(V,Ni,M);
        * raw inputs: ``stream_init(X_nodes=X, T_nodes=T,
          feature_map=fmap)`` with X:(V,Ni,D) — on fusable maps the
          hidden matrices are never materialized (fused kernel /
          streaming scan).

        Requires a DCELMRule.
        """
        C, V = self._ridge_constants()
        if X_nodes is not None:
            if H_nodes is not None:
                raise ValueError("pass either H_nodes or X_nodes, not both")
            if feature_map is None:
                raise ValueError("X_nodes requires feature_map=")
            if T_nodes is None:
                raise ValueError("X_nodes requires T_nodes= targets")
            from repro.core import stats as stats_lib

            if T_nodes.ndim == 2:
                T_nodes = T_nodes[..., None]

            def node(x, t):
                P_, Q_ = stats_lib.raw_moments(
                    x, t, feature_map,
                    dtype=stats_lib.accum_dtype(x, t),
                )
                return online.OnlineNodeState(
                    omega=stats_lib.omega_from_moments(P_, C, V), Q=Q_
                )

            per_node = jax.vmap(node)
            mesh = getattr(self.mixer, "mesh", None)
            if mesh is not None:
                # one node per device: each shard runs its own stats
                # pass where its data already lives
                spec = self.mixer.node_pspec()
                per_node = jax.jit(compat.shard_map(
                    per_node, mesh, in_specs=(spec, spec), out_specs=spec,
                    check_vma=False,
                ))
            states = per_node(X_nodes, T_nodes)
        else:
            states = jax.vmap(lambda h, t: online.init_state(h, t, C, V))(
                H_nodes, T_nodes
            )
        return StreamState(
            omegas=states.omega, Qs=states.Q, betas=online.reseed_betas(states)
        )

    def stream_chunk(
        self,
        state: "StreamState",
        added=None,
        removed=None,
        *,
        gamma,
        num_iters: int,
        trace_fn=None,
        state_spec=None,
        aux_spec=None,
        publish_to=None,
        check_gamma=True,
    ):
        """One Algorithm 2 event on every node, end-to-end.

        added/removed: optional (dH, dT) pairs with stacked shapes
        (V, dN, L)/(V, dN, M). Steps 5-12: Woodbury remove-then-add in
        O(L^2 dN) per node; step 13: re-seed beta_i = Omega_i Q_i (which
        restores the zero-gradient-sum invariant); then ``num_iters``
        consensus rounds toward the new centralized solution. Works on
        both mixers — on PpermuteMixer the stat updates are node-local
        batched ops and only the rounds touch the ICI.

        Node-level churn (a whole member arriving/departing, not just
        its data chunks) is ``stream_leave``/``stream_join``, which
        rebuild the engine for the new V. After the event,
        ``self.wire_stats`` holds the exact bytes the event's rounds
        move, counted from shapes when the rounds are traced (so under
        ``jax.jit`` it is set when the program is traced, not per
        executed event).

        publish_to: optional ``serving.BetaStore`` (anything with a
        ``publish(betas)`` method) — the post-consensus stacked betas
        are published as a fresh versioned snapshot, so a live
        ``serving.ELMServer`` hot-swaps onto the new model mid-traffic
        while the next chunks keep streaming (the serve-while-train
        loop; DESIGN.md §11).

        Returns (StreamState, traces or None).
        """
        self._ridge_constants()  # assert a DCELMRule before any work
        ostate = online.OnlineNodeState(omega=state.omegas, Q=state.Qs)
        if removed is not None:
            ostate = online.batched_remove_chunk(ostate, *removed)
        if added is not None:
            ostate = online.batched_add_chunk(ostate, *added)
        betas = online.reseed_betas(ostate)
        final, traces = self.run(
            betas,
            ostate.omega,
            gamma,
            num_iters,
            trace_fn=trace_fn,
            state_spec=state_spec,
            aux_spec=aux_spec,
            check_gamma=check_gamma,
        )
        if publish_to is not None:
            publish_to.publish(final)
        return (
            StreamState(omegas=ostate.omega, Qs=ostate.Q, betas=final),
            traces,
        )

    # -- elastic membership (beyond-paper: Algorithm 2 under churn) --------

    def stream_leave(
        self, state: "StreamState", node: int, *, graph: Graph | None = None
    ) -> tuple["ConsensusEngine", "StreamState"]:
        """Node ``node`` departs the network, taking its whole shard
        (data, statistics, estimate) with it.

        The centralized target becomes the solution over the remaining
        V-1 nodes' data, and V itself sits inside every surviving
        Omega_j through the ridge term I/(VC) — so each survivor
        re-targets its preconditioner with a rank-L Woodbury update
        (``online.rescale_num_nodes``) and re-seeds beta_j = Omega_j Q_j,
        restoring the zero-gradient-sum invariant for the smaller
        network. Returns ``(new_engine, new_state)`` — the engine is
        rebuilt for the (V-1)-node rule and topology, and
        ``new_engine.gamma_upper_bound()`` is the post-churn Thm. 2
        bound to step with (the pre-churn gamma may now be invalid).

        graph: the surviving communication graph; default = the base
        adjacency with ``node``'s row/column deleted (every snapshot,
        for time-varying bases). Membership is a data-plane change and
        needs re-stacked arrays, so it is a DenseMixer feature; on the
        sharded path model *link* loss with a FaultyMixer instead (the
        mesh shard cannot leave the physical device).
        """
        C, V = self._ridge_constants()
        if not 0 <= node < V:
            raise ValueError(f"node {node} out of range for V={V}")
        adjacencies = self._membership_adjacencies(graph, drop=node)
        keep = [i for i in range(V) if i != node]
        omegas = online.batched_rescale_num_nodes(
            state.omegas[jnp.asarray(keep)], V, V - 1, C
        )
        Qs = state.Qs[jnp.asarray(keep)]
        ostate = online.OnlineNodeState(omega=omegas, Q=Qs)
        new_engine = self._rewrap_faults(
            ConsensusEngine(
                self._dense_mixer_cls()(
                    adjacencies, compress=self._base_compress()
                ),
                DCELMRule(V - 1, C),
                secure=self.secure,
            ),
            drop=node,
        )
        return new_engine, StreamState(
            omegas=omegas, Qs=Qs, betas=online.reseed_betas(ostate)
        )

    def stream_join(
        self,
        state: "StreamState",
        H_new: jax.Array,
        T_new: jax.Array,
        *,
        graph: Graph | None = None,
    ) -> tuple["ConsensusEngine", "StreamState"]:
        """A new node joins with local data H_new:(Nn, L), T_new:(Nn, M).

        The joiner builds its statistics from scratch at the new
        network size; every incumbent re-targets Omega for V -> V+1 via
        the same rank-L Woodbury rescale and re-seeds. The joiner takes
        index V (append order). Returns ``(new_engine, new_state)``.

        graph: the enlarged communication graph; default = the base
        adjacency with the joiner connected to every incumbent — which
        jumps d_max to ~V, so a pre-churn gamma is very likely above
        the new Thm. 2 bound. Step with
        ``new_engine.gamma_upper_bound()`` /
        ``new_engine.mixer.default_gamma()``; the engine's gamma
        validation rejects a stale concrete gamma at run entry.
        """
        C, V = self._ridge_constants()
        adjacencies = self._membership_adjacencies(graph, add=True)
        omegas = online.batched_rescale_num_nodes(state.omegas, V, V + 1, C)
        joiner = online.init_state(H_new, T_new, C, V + 1)
        omegas = jnp.concatenate([omegas, joiner.omega[None]], axis=0)
        Qs = jnp.concatenate([state.Qs, joiner.Q[None]], axis=0)
        ostate = online.OnlineNodeState(omega=omegas, Q=Qs)
        new_engine = self._rewrap_faults(
            ConsensusEngine(
                self._dense_mixer_cls()(
                    adjacencies, compress=self._base_compress()
                ),
                DCELMRule(V + 1, C),
                secure=self.secure,
            ),
            add=True,
        )
        return new_engine, StreamState(
            omegas=omegas, Qs=Qs, betas=online.reseed_betas(ostate)
        )

    def _membership_adjacencies(
        self, graph: Graph | None, *, drop: int | None = None,
        add: bool = False,
    ) -> jnp.ndarray:
        """Adjacency snapshots for the post-churn network."""
        if graph is not None:
            return jnp.asarray(graph.adjacency, jnp.float32)[None]
        mixer = self.mixer
        while isinstance(mixer, (CompressedMixer, FaultyMixer)):
            mixer = mixer.base
        if not isinstance(mixer, DenseMixer):
            raise TypeError(
                "elastic membership resizes the stacked node axis and so "
                "needs a DenseMixer engine (or an explicit `graph=`); on "
                "the sharded path model link loss with a FaultyMixer"
            )
        adj = np.asarray(mixer.adjacencies)
        if drop is not None:
            adj = np.delete(np.delete(adj, drop, axis=1), drop, axis=2)
        if add:
            S, V = adj.shape[0], adj.shape[1]
            new = np.zeros((S, V + 1, V + 1), dtype=adj.dtype)
            new[:, :V, :V] = adj
            new[:, V, :V] = 1.0
            new[:, :V, V] = 1.0
            adj = new
        return jnp.asarray(adj)

    def _rewrap_faults(
        self, new_engine: "ConsensusEngine", *, drop: int | None = None,
        add: bool = False,
    ) -> "ConsensusEngine":
        """Carry FaultyMixer / CompressedMixer wrappers across a
        membership change.

        Fault masks are resized like the adjacency (departed row/column
        deleted; a joiner's links start all-up); a compression spec is
        re-applied on top unchanged. The transformed fault trace has
        NOT been re-certified for joint connectivity — re-run
        ``FaultModel.certify_jointly_connected`` on it if the churned
        network must keep the convergence guarantee.
        """
        mixer = self.mixer
        comp = mixer.spec if isinstance(mixer, CompressedMixer) else None
        if comp is not None:
            mixer = mixer.base
        if isinstance(mixer, FaultyMixer):
            keep = mixer.edge_keep
            if drop is not None:
                keep = np.delete(
                    np.delete(keep, drop, axis=1), drop, axis=2
                )
            if add:
                R, V = keep.shape[0], keep.shape[1]
                grown = np.ones((R, V + 1, V + 1), dtype=keep.dtype)
                grown[:, :V, :V] = keep
                keep = grown
            new_engine = with_faults(new_engine, keep)
        if comp is not None:
            new_engine = with_compression(new_engine, comp)
        return new_engine

    def _base_compress(self):
        return getattr(self.mixer, "compress", None)

    def _dense_mixer_cls(self) -> type:
        """The dense-layout mixer class membership churn rebuilds with —
        preserving a NeighborMixer (or other DenseMixer subclass)
        through the CompressedMixer/FaultyMixer wrapper chain, so e.g.
        a fused-kernel engine stays fused after stream_leave/join."""
        mixer = self.mixer
        while isinstance(mixer, (CompressedMixer, FaultyMixer)):
            mixer = mixer.base
        cls = type(mixer)
        return cls if issubclass(cls, DenseMixer) else DenseMixer

    def _ridge_constants(self) -> tuple[float, int]:
        if not isinstance(self.rule, DCELMRule):
            raise TypeError(
                "streaming (Algorithm 2) re-seeds beta = Omega @ Q and so "
                f"requires a DCELMRule, got {type(self.rule).__name__}"
            )
        return self.rule.C, self.rule.num_nodes


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StreamState:
    """Stacked per-node streaming state (Algorithm 2 carry).

    omegas: (V, L, L) current (I/(VC) + P_i)^{-1}
    Qs:     (V, L, M) current H_i^T T_i
    betas:  (V, L, M) node estimates after the last consensus rounds
    """

    omegas: jax.Array
    Qs: jax.Array
    betas: jax.Array


# ---------------------------------------------------------------------------
# Convenience constructors
# ---------------------------------------------------------------------------


def simulated_dc_elm(
    graphs: Graph | list[Graph] | jax.Array,
    C: float,
    *,
    dtype=jnp.float32,
    compress=None,
    mixer: str = "dense",
) -> ConsensusEngine:
    """DC-ELM over arbitrary dense graphs (the fidelity/simulation path).

    compress: None/"none" (default), "bf16" (inline payload cast), or an
    "int8"/"topk" mode string / ``compression.CompressionSpec`` (wraps
    the mixer in a ``CompressedMixer``).

    mixer: "dense" (default) mixes via the dense adjacency matmul;
    "neighbor" selects ``mixers.NeighborMixer`` — the fused gossip
    kernel plane over padded neighbor lists (dense-parity pinned), which
    falls back to the dense program on graphs too dense for gathers to
    win.
    """
    inline, spec = _split_compress(compress)
    try:
        cls = {"dense": DenseMixer, "neighbor": NeighborMixer}[mixer]
    except KeyError:
        raise ValueError(
            f'mixer must be "dense" or "neighbor", got {mixer!r}'
        ) from None
    if isinstance(graphs, (Graph, list)):
        mx = cls.from_graphs(graphs, dtype=dtype, compress=inline)
    else:
        mx = cls(graphs, compress=inline)
    eng = ConsensusEngine(mx, DCELMRule(mx.num_nodes, C))
    return with_compression(eng, spec) if spec is not None else eng


def sharded_dc_elm(
    mesh: jax.sharding.Mesh,
    spec: gossip.GossipSpec,
    C: float,
    *,
    compress=None,
) -> ConsensusEngine:
    """DC-ELM over mesh neighbors (the ppermute production path).

    compress: same knob as ``simulated_dc_elm`` — inline "bf16" or a
    ``CompressionSpec``/mode string for the compressed-gossip subsystem.
    """
    inline, cspec = _split_compress(compress)
    mixer = PpermuteMixer.for_mesh(mesh, spec, compress=inline)
    eng = ConsensusEngine(mixer, DCELMRule(mixer.num_nodes, C))
    return with_compression(eng, cspec) if cspec is not None else eng


def with_faults(
    eng: ConsensusEngine,
    faults,
    num_rounds: int | None = None,
) -> ConsensusEngine:
    """Wrap an engine's mixer in a ``FaultyMixer``.

    faults: a ``consensus.FaultModel`` (then ``num_rounds`` sets the
    fault-trace period) or a ready (R, V, V) edge keep-mask array. The
    update rule, step bound, and — on the sharded path — the compiled
    collective program are untouched; only dropped links stop
    contributing to the Laplacian.

    Stacks with compression: if the engine is already compressed, the
    fault layer slides *under* the ``CompressedMixer`` so encoded
    payloads cross whatever links the trace left alive.
    """
    if isinstance(eng.mixer, CompressedMixer):
        inner = with_faults(
            dataclasses.replace(eng, mixer=eng.mixer.base),
            faults, num_rounds,
        )
        return with_compression(inner, eng.mixer.spec)
    if isinstance(faults, FaultModel):
        if num_rounds is None:
            raise ValueError("num_rounds is required with a FaultModel")
        mixer = FaultyMixer.from_fault_model(eng.mixer, faults, num_rounds)
    else:
        mixer = FaultyMixer(eng.mixer, faults)
    return dataclasses.replace(eng, mixer=mixer)


def with_compression(eng: ConsensusEngine, spec) -> ConsensusEngine:
    """Wrap an engine's mixer in a ``compression.CompressedMixer``.

    spec: a ``CompressionSpec``, a mode string ("bf16" / "int8" /
    "topk"), or None/"none" (still wraps — useful for uniform wire
    accounting). Composes over a fault-injected engine; the update rule
    and Thm. 2 step bound are untouched (DESIGN.md §9).
    """
    return dataclasses.replace(eng, mixer=CompressedMixer(eng.mixer, spec))


def with_secure_aggregation(eng: ConsensusEngine, spec=True) -> ConsensusEngine:
    """Attach a secure-aggregation policy to an engine.

    spec: a ``secure.SecureAggregationSpec``, an int (shared PRNG
    seed), or True for the defaults. The vertical plane
    (``core/vertical.py``) reads ``eng.secure`` and applies pairwise
    additive masks — fixed-point, canceling exactly in the sum — to
    its assembly payloads; see the class docstring for why per-round
    Laplacian gossip is out of scope. Composes freely with
    ``with_faults`` (crash-time mask recovery rides the same
    ``FaultModel``) and ``with_compression``.
    """
    from repro.core.secure import SecureAggregationSpec

    return dataclasses.replace(
        eng, secure=SecureAggregationSpec.parse(spec)
    )


def _split_compress(compress):
    """Constructor ``compress=`` knob -> (inline mixer mode, spec).

    None/"none"/"bf16" ride the mixers' inline payload cast; a richer
    mode string or a ``CompressionSpec`` becomes a ``CompressedMixer``
    wrap (so ``simulated_dc_elm(g, C, compress=CompressionSpec(...))``
    just works).
    """
    if compress is None or compress in ("none", "bf16"):
        return compress, None
    return None, CompressionSpec.parse(compress)


def simulated_averaging(adjacency, *, compress=None) -> ConsensusEngine:
    """Plain consensus averaging / D-PSGD mixing on a dense adjacency."""
    inline, spec = _split_compress(compress)
    eng = ConsensusEngine(
        DenseMixer(adjacency, compress=inline), AverageRule()
    )
    return with_compression(eng, spec) if spec is not None else eng


def sharded_averaging(
    spec: gossip.GossipSpec,
    axis_sizes: dict,
    *,
    mesh: jax.sharding.Mesh | None = None,
    compress=None,
) -> ConsensusEngine:
    """Plain consensus averaging / D-PSGD mixing via ppermute gossip."""
    inline, cspec = _split_compress(compress)
    eng = ConsensusEngine(
        PpermuteMixer(
            spec=spec, axis_sizes=dict(axis_sizes), mesh=mesh,
            compress=inline,
        ),
        AverageRule(),
    )
    return with_compression(eng, cspec) if cspec is not None else eng
