"""Communication graphs and consensus machinery (paper Sec. III-A).

The network is an undirected, connected, static V-node graph G(V, E)
with adjacency A (a_ii = 0, a_ij > 0 iff (i,j) in E), degree matrix
D = diag(d_i), Laplacian Lap = D - A. Connectivity <=> lambda_2(Lap) > 0
(algebraic connectivity). The DC-ELM step size must satisfy
0 < gamma < 1/d_max (paper Thm. 2).

Two families of graphs:
  * "simulation" graphs — anything, incl. the paper's random geometric
    graphs (Fig. 6); used by the vmap-simulated DC-ELM and the paper's
    fidelity experiments (examples/paper_figs.py).
  * "ICI-realizable" graphs — ring / 2-D torus / hypercube / complete —
    whose edge sets decompose into a handful of device permutations, so
    the sharded path lowers to jax.lax.ppermute schedules (see
    core/gossip.py).
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """An undirected weighted communication graph."""

    adjacency: np.ndarray  # (V, V), symmetric, zero diagonal
    name: str = "graph"

    def __post_init__(self):
        a = self.adjacency
        if a.ndim != 2 or a.shape[0] != a.shape[1]:
            raise ValueError("adjacency must be square")
        if not np.allclose(a, a.T):
            raise ValueError("graph must be undirected (A symmetric)")
        if np.any(np.diag(a) != 0):
            raise ValueError("a_ii must be 0")
        if np.any(a < 0):
            raise ValueError("edge weights must be nonnegative")

    @property
    def num_nodes(self) -> int:
        return self.adjacency.shape[0]

    @property
    def degrees(self) -> np.ndarray:
        return self.adjacency.sum(axis=1)

    @property
    def d_max(self) -> float:
        return float(self.degrees.max())

    @property
    def laplacian(self) -> np.ndarray:
        return np.diag(self.degrees) - self.adjacency

    @property
    def algebraic_connectivity(self) -> float:
        """lambda_2 of the Laplacian; > 0 iff connected."""
        eig = np.linalg.eigvalsh(self.laplacian)
        return float(eig[1])

    @property
    def is_connected(self) -> bool:
        return self.algebraic_connectivity > 1e-9

    def neighbors(self, i: int) -> np.ndarray:
        return np.nonzero(self.adjacency[i])[0]

    def gamma_upper_bound(self) -> float:
        """Paper Thm. 2: 0 < gamma < 1/d_max."""
        return 1.0 / self.d_max

    def default_gamma(self, safety: float = 0.9) -> float:
        return safety * self.gamma_upper_bound()

    def metropolis_weights(self) -> np.ndarray:
        """Doubly-stochastic Metropolis–Hastings mixing weights.

        Not used by the paper's algorithm (which mixes with the raw
        Laplacian), but used by the beyond-paper D-PSGD trainer where a
        doubly-stochastic W gives the standard decentralized-SGD
        guarantees.
        """
        a = (self.adjacency > 0).astype(np.float64)
        deg = a.sum(1)
        W = np.zeros_like(a)
        V = self.num_nodes
        for i in range(V):
            for j in range(V):
                if i != j and a[i, j] > 0:
                    W[i, j] = 1.0 / (1.0 + max(deg[i], deg[j]))
        for i in range(V):
            W[i, i] = 1.0 - W[i].sum()
        return W


# ---------------------------------------------------------------------------
# Graph constructors
# ---------------------------------------------------------------------------


def line(V: int) -> Graph:
    a = np.zeros((V, V))
    for i in range(V - 1):
        a[i, i + 1] = a[i + 1, i] = 1.0
    return Graph(a, name=f"line{V}")


def ring(V: int) -> Graph:
    if V < 3:
        return line(V)
    a = np.zeros((V, V))
    for i in range(V):
        j = (i + 1) % V
        a[i, j] = a[j, i] = 1.0
    return Graph(a, name=f"ring{V}")


def complete(V: int) -> Graph:
    a = np.ones((V, V)) - np.eye(V)
    return Graph(a, name=f"complete{V}")


def star(V: int) -> Graph:
    """Fusion-center-like topology (for contrast experiments)."""
    a = np.zeros((V, V))
    a[0, 1:] = a[1:, 0] = 1.0
    return Graph(a, name=f"star{V}")


def torus2d(rows: int, cols: int) -> Graph:
    """2-D torus — matches TPU ICI physical topology."""
    V = rows * cols
    a = np.zeros((V, V))

    def idx(r, c):
        return (r % rows) * cols + (c % cols)

    for r in range(rows):
        for c in range(cols):
            i = idx(r, c)
            for j in (idx(r + 1, c), idx(r, c + 1)):
                if i != j:
                    a[i, j] = a[j, i] = 1.0
    return Graph(a, name=f"torus{rows}x{cols}")


def hypercube(dim: int) -> Graph:
    """2^dim-node hypercube: log-diameter, great algebraic connectivity."""
    V = 1 << dim
    a = np.zeros((V, V))
    for i in range(V):
        for b in range(dim):
            j = i ^ (1 << b)
            a[i, j] = a[j, i] = 1.0
    return Graph(a, name=f"hypercube{dim}")


def paper_fig2() -> Graph:
    """The paper's Fig. 2 network: V=4, d_max=2 (a 4-cycle)."""
    return Graph(ring(4).adjacency, name="paper_fig2")


def alternating_halves(V: int) -> list[Graph]:
    """A jointly-connected time-varying sequence whose snapshots are each
    DISCONNECTED: round 0 links even pairs (0-1)(2-3)..., round 1 links
    odd pairs (1-2)(3-4)... plus the wrap edge. The union is the V-ring.
    Exercises the paper's Sec. V time-varying-topology future work."""
    a0 = np.zeros((V, V))
    a1 = np.zeros((V, V))
    for i in range(0, V - 1, 2):
        a0[i, i + 1] = a0[i + 1, i] = 1.0
    for i in range(1, V - 1, 2):
        a1[i, i + 1] = a1[i + 1, i] = 1.0
    if V % 2 == 0 and V > 2:
        a1[0, V - 1] = a1[V - 1, 0] = 1.0
    return [Graph(a0, name=f"even_pairs{V}"), Graph(a1, name=f"odd_pairs{V}")]


def random_geometric(
    V: int, radius: float, seed: int = 0, max_tries: int = 200
) -> Graph:
    """Random geometric graph on the unit square (paper Fig. 6 style).

    Nodes connect iff closer than `radius`. Resamples until connected.
    """
    rng = np.random.default_rng(seed)
    for _ in range(max_tries):
        pts = rng.uniform(size=(V, 2))
        d = np.linalg.norm(pts[:, None, :] - pts[None, :, :], axis=-1)
        a = ((d < radius) & ~np.eye(V, dtype=bool)).astype(np.float64)
        g = Graph(a, name=f"rgg{V}")
        if g.is_connected:
            return g
    raise RuntimeError(f"no connected RGG after {max_tries} tries; grow radius")


_BUILDERS = {
    "line": line,
    "ring": ring,
    "complete": complete,
    "star": star,
    "hypercube": hypercube,
}


def build(kind: str, V: int) -> Graph:
    """Build a named topology with V nodes (used by config files)."""
    if kind == "hypercube":
        dim = int(np.log2(V))
        if 1 << dim != V:
            raise ValueError(f"hypercube needs power-of-two V, got {V}")
        return hypercube(dim)
    if kind == "torus":
        r = int(np.sqrt(V))
        while V % r:
            r -= 1
        return torus2d(r, V // r)
    if kind in _BUILDERS:
        return _BUILDERS[kind](V)
    raise ValueError(f"unknown graph kind {kind!r}")


# ---------------------------------------------------------------------------
# Fault injection (paper Sec. V: time-varying / unreliable links)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinkOutage:
    """A correlated burst outage: edge (i, j) is down for rounds
    [start, start + duration)."""

    edge: tuple[int, int]
    start: int
    duration: int


@dataclasses.dataclass(frozen=True)
class NodeCrash:
    """Node crash/rejoin: every edge incident to ``node`` is down for
    rounds [start, start + duration); the node rejoins afterwards.

    The node's *process* stays up (it keeps its state and local data);
    only its links die — the paper's communication-failure model. Data
    level churn (the node's shard leaving the problem) is
    ``ConsensusEngine.stream_leave``/``stream_join``.
    """

    node: int
    start: int
    duration: int


@dataclasses.dataclass(frozen=True)
class FaultModel:
    """Generates per-round edge keep-masks over a base graph.

    Three composable failure processes (all applied on top of each
    other, worst wins):

    * ``edge_drop_prob`` — i.i.d. per-round Bernoulli loss of each
      undirected edge (packet loss / flaky link).
    * ``outages`` — scheduled correlated bursts: a link down for a
      contiguous round interval.
    * ``crashes`` — scheduled node crash/rejoin: all of a node's links
      down for a contiguous round interval.

    ``edge_keep(R)`` is deterministic in ``seed``, so the simulated
    (DenseMixer) and sharded (PpermuteMixer) execution paths of a
    ``FaultyMixer`` can replay the *same* fault trace and be compared
    bit-for-bit-level close. Consumers index round k with mask k % R.

    Theorem 2's convergence survives faults as long as the masked graph
    sequence stays *jointly connected* — every window of W consecutive
    rounds has a connected union graph. ``certify_jointly_connected``
    checks that (cyclically, matching the k % R replay), and
    ``sample_certified`` searches seeds until it holds.
    """

    graph: Graph
    edge_drop_prob: float = 0.0
    outages: tuple[LinkOutage, ...] = ()
    crashes: tuple[NodeCrash, ...] = ()
    seed: int = 0

    def __post_init__(self):
        if not 0.0 <= self.edge_drop_prob < 1.0:
            raise ValueError("edge_drop_prob must be in [0, 1)")
        V = self.graph.num_nodes
        for o in self.outages:
            i, j = o.edge
            if not (0 <= i < V and 0 <= j < V) or i == j:
                raise ValueError(f"bad outage edge {o.edge}")
            if self.graph.adjacency[i, j] == 0:
                # silently accepted before, then erased by the
                # `keep * edges` mask — the outage could never fire
                raise ValueError(
                    f"outage edge {o.edge} is not an edge of "
                    f"{self.graph.name}: the keep-mask is applied over "
                    "the base edge set, so this outage could never fire"
                )
            if o.start < 0 or o.duration < 0:
                raise ValueError(
                    f"outage on {o.edge} has negative start/duration "
                    f"({o.start}, {o.duration}); intervals are "
                    "[start, start + duration) in rounds >= 0"
                )
        for c in self.crashes:
            if not 0 <= c.node < V:
                raise ValueError(f"bad crash node {c.node}")
            if c.start < 0 or c.duration < 0:
                raise ValueError(
                    f"crash of node {c.node} has negative start/duration "
                    f"({c.start}, {c.duration}); intervals are "
                    "[start, start + duration) in rounds >= 0"
                )

    @property
    def num_nodes(self) -> int:
        return self.graph.num_nodes

    def edge_keep(self, num_rounds: int) -> np.ndarray:
        """(R, V, V) symmetric 0/1 keep-masks over the base edge set."""
        V = self.num_nodes
        R = int(num_rounds)
        edges = (self.graph.adjacency > 0).astype(np.float64)
        keep = np.ones((R, V, V))
        if self.edge_drop_prob > 0.0:
            rng = np.random.default_rng(self.seed)
            u = rng.random((R, V, V))
            u = np.triu(u, 1)
            u = u + np.transpose(u, (0, 2, 1))  # symmetric per-edge draws
            keep *= (u >= self.edge_drop_prob).astype(np.float64)
        for o in self.outages:
            i, j = o.edge
            lo, hi = max(o.start, 0), min(o.start + o.duration, R)
            keep[lo:hi, i, j] = keep[lo:hi, j, i] = 0.0
        for c in self.crashes:
            lo, hi = max(c.start, 0), min(c.start + c.duration, R)
            keep[lo:hi, c.node, :] = 0.0
            keep[lo:hi, :, c.node] = 0.0
        return keep * edges[None]

    def adjacency_stream(self, num_rounds: int) -> np.ndarray:
        """(R, V, V) masked adjacency snapshots A_k = A * keep_k."""
        return self.edge_keep(num_rounds) * np.asarray(self.graph.adjacency)[None]

    def graphs(self, num_rounds: int) -> list[Graph]:
        return [
            Graph(a, name=f"{self.graph.name}_fault{k}")
            for k, a in enumerate(self.adjacency_stream(num_rounds))
        ]

    def gamma_upper_bound(self) -> float:
        """Faults only *remove* edges, so d_max never grows: the base
        graph's Thm. 2 bound stays valid for every masked snapshot."""
        return self.graph.gamma_upper_bound()

    def certify_jointly_connected(
        self, num_rounds: int, window: int
    ) -> bool:
        """True iff every (cyclic) window of ``window`` consecutive
        masked snapshots has a connected union graph.

        Cyclic because consumers replay mask k % R forever; the fault
        trace is effectively periodic.
        """
        stream = self.adjacency_stream(num_rounds)
        R = stream.shape[0]
        if window <= 0:
            raise ValueError("window must be positive")
        if window >= R:
            union = stream.max(axis=0)
            return Graph(union, name="union").is_connected
        for s in range(R):
            idx = [(s + t) % R for t in range(window)]
            union = stream[idx].max(axis=0)
            if not Graph(union, name="union").is_connected:
                return False
        return True

    @classmethod
    def sample_certified(
        cls,
        graph: Graph,
        edge_drop_prob: float,
        num_rounds: int,
        window: int,
        *,
        outages: tuple[LinkOutage, ...] = (),
        crashes: tuple[NodeCrash, ...] = (),
        seed: int = 0,
        max_tries: int = 50,
    ) -> "FaultModel":
        """Search seeds until the fault trace is jointly connected."""
        for s in range(seed, seed + max_tries):
            fm = cls(
                graph=graph,
                edge_drop_prob=edge_drop_prob,
                outages=outages,
                crashes=crashes,
                seed=s,
            )
            if fm.certify_jointly_connected(num_rounds, window):
                return fm
        raise RuntimeError(
            f"no jointly connected fault trace in {max_tries} seeds "
            f"(p={edge_drop_prob}, window={window}); grow the window or "
            "lower the failure rate"
        )


@dataclasses.dataclass(frozen=True)
class DelayModel:
    """Per-message link-latency distribution for the async runtime.

    A message put on edge (i, j) at virtual time t is delivered at

        t + scale(i, j) * (base + jitter * U),   U ~ Uniform[0, 1)

    with U drawn from the scheduler's seeded stream, so a whole async
    run replays bit-for-bit from its seed. ``edge_scale`` entries model
    slow *links* (undirected: (i, j) covers both directions); a slow
    *node* is a firing-period concern and lives in
    ``async_engine.AsyncEngine(fire_periods=...)``. ``base=0`` with no
    jitter is the synchronous limit: delivery at the send instant,
    consumed at the receiver's next fire.

    Complementary to ``FaultModel``: FaultModel decides whether a
    message survives the link at all, DelayModel decides when the
    survivors arrive.
    """

    base: float = 0.1
    jitter: float = 0.0
    edge_scale: tuple[tuple[tuple[int, int], float], ...] = ()

    def __post_init__(self):
        if not np.isfinite(self.base) or self.base < 0.0:
            raise ValueError(f"base delay must be finite >= 0, got {self.base}")
        if not np.isfinite(self.jitter) or self.jitter < 0.0:
            raise ValueError(f"jitter must be finite >= 0, got {self.jitter}")
        for (i, j), s in self.edge_scale:
            if i == j:
                raise ValueError(f"edge_scale on a self-loop ({i}, {j})")
            if not np.isfinite(s) or s <= 0.0:
                raise ValueError(
                    f"edge_scale for ({i}, {j}) must be finite > 0, got {s}"
                )

    def scale(self, i: int, j: int) -> float:
        """Per-edge latency multiplier, symmetric in (i, j)."""
        for (a, b), s in self.edge_scale:
            if (a, b) == (i, j) or (a, b) == (j, i):
                return s
        return 1.0

    def sample(self, rng: np.random.Generator, i: int, j: int) -> float:
        """One message's latency on edge (i, j); consumes one uniform
        from ``rng`` iff the model has jitter (stream-stable in config)."""
        d = self.base
        if self.jitter > 0.0:
            d += self.jitter * float(rng.random())
        return self.scale(i, j) * d


# ---------------------------------------------------------------------------
# Convergence-rate analysis (paper Appendix C)
# ---------------------------------------------------------------------------


def dc_elm_iteration_matrix(
    graph: Graph, omegas: np.ndarray, gamma: float, VC: float
) -> np.ndarray:
    """W = I_{LV} - (gamma/VC) * Omega * (Lap kron I_L)  (paper eq. 48).

    omegas: (V, L, L) per-node Omega_i matrices.
    Only for analysis/tests (dense LV x LV).
    """
    V = graph.num_nodes
    L = omegas.shape[-1]
    lap = graph.laplacian
    big = np.kron(lap, np.eye(L))
    omega_blk = np.zeros((V * L, V * L))
    for i in range(V):
        omega_blk[i * L : (i + 1) * L, i * L : (i + 1) * L] = omegas[i]
    return np.eye(V * L) - (gamma / VC) * omega_blk @ big


def essential_spectral_radius(W: np.ndarray, L: int) -> float:
    """Second-largest eigenvalue modulus — the exponential consensus rate.

    For the DC-ELM iteration matrix the eigenvalue 1 has multiplicity L
    (one per output-weight coordinate); the rate is the largest of the
    remaining moduli.
    """
    ev = np.sort(np.abs(np.linalg.eigvals(W)))[::-1]
    return float(ev[L])
