"""Streamed chunks: Algorithm 2, one chunk of new rows on every node.

A chunk is the feature map over the new rows, then
``ConsensusEngine.stream_chunk`` (Woodbury add, re-seed, the first K
rounds), then further K-round blocks until the consensus residual is at
most epsilon, checked on the device (``loops.converge``): the host waits
once a chunk. ``stream_init`` runs in set-up. The chunks are made on the
device in set-up, a ring of ``ring`` buffers, so that the generator
stays out of the window: the deployment's rows, fixed by its
``data_seed``, each node's in an order drawn from the run's seed.
"""

from __future__ import annotations

import dataclasses

import jax

from bench import harness, loops, work
from bench.network import Checked, Network
from bench.reference import consensus_error


class Driver(Checked):
    def __init__(self, cfg, traffic, seed, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.net = Network(cfg, devices)
        self.refs = {}

    def setup(self):
        from repro.core.features import RandomFeatureMap

        net, cfg, tr = self.net, self.cfg, self.traffic
        k_order = loops.key(self.seed)
        self.X, self.T = net.data(0, net.Ni, k_order)
        self.W, self.b = net.features()
        self.ring = [
            net.data(1 + c, tr["chunk_rows"], jax.random.fold_in(k_order, 1 + c))
            for c in range(tr["ring"])
        ]
        eng = net.engine()
        gamma, K, act = net.gamma, cfg["K"], net.activation

        def init(X, T, W, b):
            return eng.stream_init(
                X_nodes=X, T_nodes=T, feature_map=RandomFeatureMap(W, b, act)
            )

        def chunk(state, dX, dT, W, b):
            dH = RandomFeatureMap(W, b, act)(dX)
            return eng.stream_chunk(
                state, added=(dH, dT), gamma=gamma, num_iters=K
            )[0]

        def settle(betas, omegas):
            return loops.converge(
                betas, lambda b: eng.run(b, omegas, gamma, K)[0],
                consensus_error, eps=cfg["eps"], K=K,
                max_rounds=tr["max_rounds"], rounds=K,
            )

        self.chunk_fn = jax.jit(chunk)
        self.settle = jax.jit(settle)
        self.state = jax.jit(init)(self.X, self.T, self.W, self.b)
        self.chunks = 0
        self.last = self.step()  # warm-up: one whole chunk

    def step(self):
        with harness.span("chunk_prep"):
            dX, dT = self.ring[self.chunks % len(self.ring)]
        with harness.span("stats"):
            state = self.chunk_fn(self.state, dX, dT, self.W, self.b)
        self.chunks += 1
        with harness.span("rounds"):
            betas, rounds, reached = self.settle(state.betas, state.omegas)
        self.state = dataclasses.replace(state, betas=betas)
        with harness.span("residual_check"):
            return {"rounds": int(rounds), "reached": bool(reached)}

    def window(self, seconds):
        chunks, elapsed = loops.back_to_back(self.step, seconds)
        net, dN = self.net, self.traffic["chunk_rows"]
        rounds = sum(c["rounds"] for c in chunks)
        chunk_flops = net.V * (
            2.0 * dN * net.D * net.L + work.woodbury_flops(net.L, net.M, dN)
        )
        counters = {
            "chunks": len(chunks),
            "rounds_per_chunk": [c["rounds"] for c in chunks],
            "rounds": rounds,
            "useful_flops": len(chunks) * chunk_flops
            + rounds * work.round_flops(net.V, net.edges, net.L, net.M),
            "V": net.V, "edges": net.edges, "d_max": net.d_max,
        }
        return harness.Window(
            end_to_end={"chunk_s": elapsed / len(chunks)},
            counters=counters,
            attempted=len(chunks),
            failed=sum(not c["reached"] for c in chunks),
            seconds=elapsed,
        )

    def release(self):
        del self.chunk_fn, self.settle

    def outputs(self):
        s = self.state
        return {"Qs": s.Qs, "omegas": s.omegas, "betas": s.betas}

    def parts(self):
        """The initial rows and every chunk streamed so far (chunk c
        used ring buffer c % ring)."""
        R = len(self.ring)
        return [(self.X, self.T, 1)] + [
            (*self.ring[r], len(range(r, self.chunks, R)))
            for r in range(min(R, self.chunks))
        ]
