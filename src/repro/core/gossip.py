"""Gossip primitives: neighbor exchange via ``jax.lax.ppermute``.

This is the TPU-native realization of the paper's message-passing step
"Send beta_i to N_i, and receive beta_j, j in N_i" (Algorithm 1, step 8).
Instead of point-to-point sockets, each consensus round lowers to a
handful of ``collective-permute`` ops on the device mesh — neighbor-only
ICI traffic, **no all-reduce / no fusion center**, exactly matching the
paper's communication model.

A topology on a mesh axis is a set of edge *permutations*; applying all
permutations and summing ``(ppermute(x) - x)`` computes the Laplacian
term  sum_{j in N_i} a_ij (x_j - x_i)  with unit weights.

Supported ICI-realizable topology kinds per axis:
  ring       2 perms (+1 / -1 shifts); degree 2 (1 when axis size == 2)
  hypercube  log2(n) perms (bit flips); degree log2(n)
  complete   n-1 perms (all shifts); degree n-1
  none       no mixing on this axis

Multi-axis specs compose as a Cartesian-product (torus-like) graph:
e.g. ring on "pod" x ring on "data" = the 2 x 16 torus over 32 consensus
nodes on the multi-pod mesh.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
from jax import lax

from repro.core.scopes import phase

Perm = list[tuple[int, int]]


def ring_perms(n: int) -> list[Perm]:
    if n == 1:
        return []
    fwd: Perm = [(i, (i + 1) % n) for i in range(n)]
    if n == 2:
        return [fwd]  # +1 and -1 coincide; avoid double-counting the edge
    bwd: Perm = [(i, (i - 1) % n) for i in range(n)]
    return [fwd, bwd]


def hypercube_perms(n: int) -> list[Perm]:
    dim = int(math.log2(n))
    if 1 << dim != n:
        raise ValueError(f"hypercube axis needs power-of-two size, got {n}")
    return [[(i, i ^ (1 << b)) for i in range(n)] for b in range(dim)]


def complete_perms(n: int) -> list[Perm]:
    return [[(i, (i + s) % n) for i in range(n)] for s in range(1, n)]


_PERM_BUILDERS = {
    "ring": ring_perms,
    "hypercube": hypercube_perms,
    "complete": complete_perms,
    "none": lambda n: [],
}


@dataclasses.dataclass(frozen=True)
class GossipSpec:
    """Which mesh axes gossip, and with which topology kind.

    axes:  mesh axis names carrying consensus nodes, e.g. ("data",) or
           ("pod", "data").
    kinds: per-axis topology kind.
    """

    axes: tuple[str, ...]
    kinds: tuple[str, ...]

    def __post_init__(self):
        if len(self.axes) != len(self.kinds):
            raise ValueError("axes and kinds must have equal length")
        for k in self.kinds:
            if k not in _PERM_BUILDERS:
                raise ValueError(f"unknown topology kind {k!r}")

    def degree(self, axis_sizes: dict[str, int]) -> int:
        """Graph degree d_i (regular graphs => d_max) of the product graph."""
        deg = 0
        for ax, kind in zip(self.axes, self.kinds):
            deg += len(_PERM_BUILDERS[kind](axis_sizes[ax]))
        return deg

    def num_nodes(self, axis_sizes: dict[str, int]) -> int:
        n = 1
        for ax in self.axes:
            n *= axis_sizes[ax]
        return n

    def gamma_upper_bound(self, axis_sizes: dict[str, int]) -> float:
        """Paper Thm. 2 step-size bound 1/d_max for this product graph."""
        d = self.degree(axis_sizes)
        return 1.0 / d if d else float("inf")

    def to_graph(self, axis_sizes: dict[str, int]):
        """Dense `consensus.Graph` of the product topology (for analysis)."""
        from repro.core import consensus

        adj = np.zeros((1, 1))
        adj_graphs = []
        for ax, kind in zip(self.axes, self.kinds):
            n = axis_sizes[ax]
            a = np.zeros((n, n))
            for perm in _PERM_BUILDERS[kind](n):
                for s, d in perm:
                    if s != d:
                        a[s, d] += 1.0
            # undirected: perms come in +/- pairs (or are involutions)
            a = np.maximum(a, a.T)
            adj_graphs.append(a)
        # Cartesian product: L(G1 x G2) = L1 kron I + I kron L2
        total = adj_graphs[0]
        for a in adj_graphs[1:]:
            n1, n2 = total.shape[0], a.shape[0]
            new = np.kron(total, np.eye(n2)) + np.kron(np.eye(n1), a)
            total = new
        _ = adj
        return consensus.Graph(total, name="x".join(self.kinds))


def _axis_perms(spec: GossipSpec, axis_sizes: dict[str, int]):
    for ax, kind in zip(spec.axes, spec.kinds):
        for perm in _PERM_BUILDERS[kind](axis_sizes[ax]):
            yield ax, perm


def neighbor_laplacian(x, spec: GossipSpec, axis_sizes: dict[str, int]):
    """sum_{j in N_i} (x_j - x_i) for a pytree x, inside shard_map.

    One ppermute per edge-permutation per leaf; XLA fuses the subtract/
    accumulate. Unit edge weights (a_ij = 1), matching the paper's
    simulations. Its ops are the ``exchange`` phase.
    """

    def leaf(v):
        acc = None
        for ax, perm in _axis_perms(spec, axis_sizes):
            recv = lax.ppermute(v, ax, perm)
            d = recv - v
            acc = d if acc is None else acc + d
        if acc is None:
            return jax.numpy.zeros_like(v)
        return acc

    with phase("exchange"):
        return jax.tree.map(leaf, x)


def masked_neighbor_laplacian(
    x, spec: GossipSpec, axis_sizes: dict[str, int], keep
):
    """Laplacian term with per-permutation keep-weights for THIS node.

    ``keep`` is a (num_perms,) vector — entry p multiplies the
    contribution node i receives through edge-permutation p this round
    (0 = link down, 1 = link up). Every ppermute still executes, so the
    collective schedule (and any compiled program built over it) is
    identical to the fault-free one; a dropped link just contributes
    zero to the Laplacian. Call inside shard_map. Its ops are the
    ``exchange`` phase.
    """

    def leaf(v):
        acc = None
        p = 0
        for ax, perm in _axis_perms(spec, axis_sizes):
            recv = lax.ppermute(v, ax, perm)
            d = (recv - v) * keep[p].astype(v.dtype)
            acc = d if acc is None else acc + d
            p += 1
        if acc is None:
            return jax.numpy.zeros_like(v)
        return acc

    with phase("exchange"):
        return jax.tree.map(leaf, x)


def global_node_index(spec: GossipSpec, axis_sizes: dict[str, int]):
    """This shard's product-graph node index, row-major over spec.axes.

    Matches both ``GossipSpec.to_graph`` node numbering and the layout
    of a leading array axis sharded with PartitionSpec(spec.axes).
    Call inside shard_map.
    """
    idx = None
    for ax in spec.axes:
        i = lax.axis_index(ax)
        idx = i if idx is None else idx * axis_sizes[ax] + i
    if idx is None:
        raise ValueError("spec has no axes")
    return idx


def perm_sources(spec: GossipSpec, axis_sizes: dict[str, int]) -> np.ndarray:
    """(num_perms, V) table: src[p, i] = the node whose value node i
    receives through edge-permutation p (global product-graph indices,
    same order as ``_axis_perms``)."""
    sizes = [axis_sizes[ax] for ax in spec.axes]
    V = int(np.prod(sizes))
    coords = np.stack(np.unravel_index(np.arange(V), sizes), axis=-1)
    rows = []
    for a, (ax, kind) in enumerate(zip(spec.axes, spec.kinds)):
        n = axis_sizes[ax]
        for perm in _PERM_BUILDERS[kind](n):
            inv = np.empty(n, dtype=np.int64)  # dst -> src along axis a
            for s, d in perm:
                inv[d] = s
            c = coords.copy()
            c[:, a] = inv[c[:, a]]
            rows.append(np.ravel_multi_index(tuple(c.T), sizes))
    if not rows:
        return np.zeros((0, V), dtype=np.int64)
    return np.stack(rows).astype(np.int64)


def fold_edge_keep(
    spec: GossipSpec, axis_sizes: dict[str, int], edge_keep: np.ndarray
) -> np.ndarray:
    """Fold (R, V, V) symmetric edge keep-masks onto the ppermute
    schedule: returns (R, num_perms, V) with out[r, p, i] =
    edge_keep[r, src[p, i], i] — the weight of the in-edge node i uses
    from permutation p in round r."""
    edge_keep = np.asarray(edge_keep)
    V = spec.num_nodes(axis_sizes)
    if edge_keep.ndim != 3 or edge_keep.shape[-2:] != (V, V):
        raise ValueError(
            f"edge_keep must be (R, {V}, {V}), got {edge_keep.shape}"
        )
    src = perm_sources(spec, axis_sizes)  # (P, V)
    dst = np.arange(V)[None, :]
    return edge_keep[:, src, dst]


def neighbor_avg(x, spec: GossipSpec, axis_sizes: dict[str, int], gamma: float):
    """One plain-consensus averaging step x <- x + gamma * Lap-term."""
    lap = neighbor_laplacian(x, spec, axis_sizes)
    return jax.tree.map(lambda v, d: v + gamma * d, x, lap)


def mesh_axis_sizes(mesh: jax.sharding.Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def validate_spec(spec: GossipSpec, mesh: jax.sharding.Mesh) -> None:
    sizes = mesh_axis_sizes(mesh)
    for ax in spec.axes:
        if ax not in sizes:
            raise ValueError(f"gossip axis {ax!r} not in mesh {mesh.axis_names}")


def collective_bytes_per_round(
    spec: GossipSpec, axis_sizes: dict[str, int], payload_bytes: int
) -> int:
    """Per-node ICI bytes sent per consensus round (for roofline napkin math)."""
    return spec.degree(axis_sizes) * payload_bytes
