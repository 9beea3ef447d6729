"""What the learning and streaming cells share: the network's data,
its engine, and the reference solution over everything it has seen."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench import reference


@dataclasses.dataclass
class Network:
    """One configuration's deployment on the devices of a run."""

    cfg: dict
    devices: list

    def __post_init__(self):
        cfg = self.cfg
        self.V, self.Ni, self.D, self.L, self.M = (
            cfg[k] for k in ("V", "Ni", "D", "L", "M")
        )
        self.C = 1.0 / (self.V * self.Ni) if cfg["C"] == "1/(V*Ni)" else float(cfg["C"])
        self.activation = cfg["activation"]
        self.adjacency = reference.network(cfg)
        self.edges = int(self.adjacency.sum())
        self.d_max = int(self.adjacency.sum(axis=1).max())
        self.gamma = cfg["gamma_safety"] / self.d_max
        self.sharded = cfg["engine"] == "sharded"
        self._makers = {}
        if self.sharded:
            self.mesh = Mesh(np.asarray(self.devices), ("data",))
            self.nodes = NamedSharding(self.mesh, P("data"))
            self.replicated = NamedSharding(self.mesh, P())
        else:
            self.nodes = self.replicated = jax.sharding.SingleDeviceSharding(
                self.devices[0]
            )

    def engine(self):
        """The program's engine for this deployment."""
        from repro.core import engine, gossip

        if self.sharded:
            if self.cfg["graph"]["kind"] != "ring":
                raise ValueError("the sharded engine runs ring topologies")
            spec = gossip.GossipSpec(axes=("data",), kinds=("ring",))
            return engine.sharded_dc_elm(self.mesh, spec, self.C)
        return engine.simulated_dc_elm(
            jnp.asarray(self.adjacency), self.C, mixer="neighbor"
        )

    def data(self, block: int, rows: int, order):
        """(X, T): block ``block`` of the deployment's rows, ``rows`` on
        every node, made where they live. The rows are fixed by the
        configuration's ``data_seed``, as a deployment's dataset is;
        ``order`` (a key from the run's seed) shuffles each node's rows.
        So every seed learns the same rows in its own order: the same
        work, with each sum taken in another order. One program per
        ``rows``, whatever the block."""
        if rows not in self._makers:
            self._makers[rows] = self._maker(rows)
        return self._makers[rows](
            jax.random.key(self.cfg["data_seed"]), block, order
        )

    def _maker(self, rows: int):
        V, D, M = self.V, self.D, self.M

        def make(seed_key, block, o):
            k = jax.random.fold_in(seed_key, block)
            X, T = reference.make_data(k, (V, rows), D, M)
            perm = jax.vmap(lambda kv: jax.random.permutation(kv, rows))(
                jax.random.split(o, V)
            )
            take = jax.vmap(lambda a, p: a[p])
            return take(X, perm), take(T, perm)

        return jax.jit(make, out_shardings=(self.nodes, self.nodes))

    def features(self):
        """The deployment's hidden layer (W, b), fixed by the
        configuration's ``features_seed`` as its dataset is: the rounds
        to epsilon depend on it, so a seed that drew it would change the
        work (by 2% a chunk at ``rgg1024``)."""
        scale = self.cfg["feature_scale"]
        return jax.jit(
            lambda k: reference.make_features(k, self.D, self.L, scale),
            out_shardings=(self.replicated, self.replicated),
        )(jax.random.key(self.cfg["features_seed"]))

    def moments(self, parts, W, b, precision="highest"):
        """Per-node reference (P, Q) summed over ``parts``, a list of
        (X, T, count) node-stacked blocks of rows, on the device that
        holds each node's rows. P is returned on the first device; Q in
        float64 on the host, summed there over blocks of ``Q_ROWS`` rows,
        so that the reference's own float32 sums over a node's rows do
        not outweigh the program's (they did: 9.7e-7 of max |Q| over
        16,384 rows, three times the program's error, on a TPU v5e)."""
        first = self.devices[0]
        P_sum = None
        Q_sum = np.zeros((self.V, self.L, self.M), np.float64)
        for X, T, count in parts:
            for shard_x, shard_t in zip(_shards(X), _shards(T)):
                dev = shard_x.device
                w, bb = jax.device_put(W, dev), jax.device_put(b, dev)
                P_, Q_ = _node_moments(
                    shard_x.data, shard_t.data, w, bb, self.activation,
                    precision, self.cfg["reference_rows"],
                )
                idx = shard_x.index[0]
                P_sum = _add_at(P_sum, idx, jax.device_put(count * P_, first), self.V)
                Q_sum[idx] += count * Q_
        return P_sum, Q_sum

    def compare(self, out: dict, P_ref, Q_ref) -> dict:
        """The numbers compared: Q and Omega of every node against the
        reference moments; every node's beta against beta*; and the
        zero-gradient-sum invariant sum_i G_i beta_i = sum_i Q_i (G_i
        the reference's ridge Gram), which eq. (20) rounds keep exactly
        however far from consensus they stop."""
        first = self.devices[0]
        Qs = jax.device_put(out["Qs"], first)
        omegas = jax.device_put(out["omegas"], first)
        betas = jax.device_put(jnp.asarray(out["betas"], jnp.float32), first)
        ridge = 1.0 / (self.V * self.C)
        Q_total = np.sum(Q_ref, axis=0)
        target = reference.beta_star(jnp.sum(P_ref, axis=0), Q_total, self.C)
        return {
            "q_rel": reference.node_rel_err(Qs, Q_ref),
            "q_med": reference.node_median_err(Qs, Q_ref),
            "qsum_rel": reference.rel_err(
                np.sum(np.asarray(Qs, np.float64), axis=0), Q_total
            ),
            "omega_resid": reference.omega_residual(omegas, P_ref, ridge),
            "beta_dist": reference.distance_to(np.asarray(betas), target),
            "zgs_rel": reference.rel_err(
                reference.gram_times(P_ref, betas, ridge), Q_total
            ),
        }

    def control(self, P_c, Q_c) -> dict:
        """The reference in the program's place, from moments computed a
        precision step lower: Q, Omega and beta* broadcast to every node."""
        target = reference.beta_star(
            jnp.sum(P_c, axis=0), np.sum(Q_c, axis=0), self.C
        )
        return {
            "Qs": Q_c,
            "omegas": reference.omegas_from(P_c, 1.0 / (self.V * self.C)),
            "betas": np.broadcast_to(target, (self.V, *target.shape)),
        }


class Checked:
    """What the learning and streaming drivers share after the window:
    the reference moments over ``parts()`` (cached per precision), the
    control in the program's place, and the comparison."""

    def _reference(self, precision):
        if precision not in self.refs:
            self.refs[precision] = self.net.moments(
                self.parts(), self.W, self.b, precision
            )
        return self.refs[precision]

    def control_outputs(self):
        return self.net.control(*self._reference("high"))

    def compare(self, out):
        return self.net.compare(out, *self._reference("highest"))

    def check(self):
        return self.compare(self.outputs())


def _shards(x):
    """Node-blocks of a node-stacked array, one per device that holds
    part of it (a single-device array is one block)."""
    return sorted(x.addressable_shards, key=lambda s: s.index[0].start or 0)


def _add_at(total, idx: slice, part, V: int):
    if total is None:
        total = jnp.zeros((V, *part.shape[1:]), part.dtype, device=part.device)
    return total.at[idx].add(part)


#: rows a block of the reference's Q sums in float32 before float64 takes over
Q_ROWS = 1024


def _node_moments(X, T, W, b, activation, precision, rows):
    """(P, Q) of the nodes in X (B, N, D), a few nodes and blocks of at
    most ``Q_ROWS`` rows at a time, at most ``rows`` rows in all, so that
    the hidden rows fit beside the data: P summed over the blocks on the
    device, Q in float64 on the host."""
    B, N, _ = X.shape
    block = min(N, Q_ROWS)
    nodes = max(1, rows // block)
    Ps = []
    Q = np.zeros((B, W.shape[1], T.shape[2]), np.float64)
    for v in range(0, B, nodes):
        P_ = 0.0
        for s in range(0, N, block):
            dP, dQ = reference.node_moments(
                X[v:v + nodes, s:s + block], T[v:v + nodes, s:s + block], W, b,
                activation=activation, precision=precision,
            )
            P_ = P_ + dP
            Q[v:v + nodes] += np.asarray(dQ, np.float64)
        Ps.append(P_)
    return jnp.concatenate(Ps), Q
