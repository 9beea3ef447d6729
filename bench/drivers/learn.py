"""Learning jobs: raw per-node shards to a readout within epsilon.

A job is ``ConsensusEngine.stream_init`` (the fused stats kernel and the
Cholesky Omega of every node) and then blocks of K eq. (20) rounds of
``ConsensusEngine.run`` until the consensus residual is at most epsilon,
checked on the device (``loops.converge``): the host waits once a job.
Jobs run back to back on the data made in set-up.
"""

from __future__ import annotations

import jax

from bench import harness, loops, work
from bench.network import Checked, Network
from bench.reference import consensus_error


class Driver(Checked):
    def __init__(self, cfg, traffic, seed, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.net = Network(cfg, devices)
        self.refs = {}
        self.last = None

    def setup(self):
        from repro.core.features import RandomFeatureMap

        net, cfg = self.net, self.cfg
        k_order = loops.key(self.seed)
        self.X, self.T = net.data(0, net.Ni, k_order)
        self.W, self.b = net.features()
        eng = net.engine()
        gamma, K, act = net.gamma, cfg["K"], net.activation

        def stats(X, T, W, b):
            return eng.stream_init(
                X_nodes=X, T_nodes=T, feature_map=RandomFeatureMap(W, b, act)
            )

        def settle(betas, omegas):
            return loops.converge(
                betas, lambda b: eng.run(b, omegas, gamma, K)[0],
                consensus_error, eps=cfg["eps"], K=K,
                max_rounds=self.traffic["max_rounds"],
            )

        self.stats = jax.jit(stats)
        self.settle = jax.jit(settle)
        self.job()  # warm-up: one whole job

    def job(self):
        """One job. Its arrays replace the previous job's in ``last``, so
        that one job's Omegas (268 MB at mnist64) stay alive, not every
        job's in the window; the result holds only counts."""
        self.last = None
        with harness.span("stats"):
            state = self.stats(self.X, self.T, self.W, self.b)
        with harness.span("rounds"):
            betas, rounds, reached = self.settle(state.betas, state.omegas)
        with harness.span("residual_check"):
            rounds, reached = int(rounds), bool(reached)
        self.last = {"Qs": state.Qs, "omegas": state.omegas, "betas": betas}
        return {"rounds": rounds, "reached": reached}

    def window(self, seconds):
        jobs, elapsed = loops.back_to_back(self.job, seconds)
        net = self.net
        rounds = sum(j["rounds"] for j in jobs)
        job_flops = net.V * (
            work.stats_terms(net.Ni, net.D, net.L, net.M)[0]
            + work.omega_flops(net.L, net.M)
        )
        counters = {
            "jobs": len(jobs),
            "rounds_per_job": [j["rounds"] for j in jobs],
            "useful_flops": len(jobs) * job_flops
            + rounds * work.round_flops(net.V, net.edges, net.L, net.M),
            "V": net.V, "edges": net.edges, "d_max": net.d_max,
        }
        return harness.Window(
            end_to_end={"learn_s": elapsed / len(jobs)},
            counters=counters,
            attempted=len(jobs),
            failed=sum(not j["reached"] for j in jobs),
            seconds=elapsed,
        )

    def release(self):
        del self.stats, self.settle

    def outputs(self):
        return self.last

    def parts(self):
        return [(self.X, self.T, 1)]
