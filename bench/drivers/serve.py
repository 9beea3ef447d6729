"""Serving: an open loop of requests through ``ELMServer``.

Arrivals are Poisson at the traffic file's fixed rate and request sizes
log-uniform between ``rows_min`` and ``rows_max``; every seed gets the
same set of gaps and sizes (fixed quantiles), in its own order, so that
the seed changes the order and the data but not the work. In the window
every request whose due time has passed is submitted (to the next node
round-robin), then ``flush()`` runs. Latency is timed from the due time
to the response; a request never answered counts as infinitely late.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, loops, reference, work


class Driver:
    def __init__(self, cfg, traffic, seed, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.rate = float(traffic["rate_per_s"])

    def setup(self):
        from repro.core.features import RandomFeatureMap
        from repro.serving import BetaStore, ELMServer

        cfg, tr = self.cfg, self.traffic
        D, L, M, V = cfg["D"], cfg["L"], cfg["M"], cfg["V"]
        k_rows, k_beta = jax.random.split(loops.key(self.seed))
        # the deployment's shared hidden layer, fixed like its graph: the
        # server compiles it into each bucket's program
        self.W, self.b = jax.jit(
            lambda k: reference.make_features(k, D, L, cfg["feature_scale"])
        )(jax.random.key(tr["features_seed"]))
        self.betas = jax.jit(
            lambda k: jax.random.normal(k, (V, L, M), jnp.float32) / math.sqrt(L)
        )(k_beta)
        self.pool = np.asarray(jax.jit(
            lambda k: jax.random.uniform(k, (tr["pool_rows"], D), jnp.float32)
        )(k_rows))
        self.server = ELMServer(
            RandomFeatureMap(self.W, self.b, cfg["activation"]),
            BetaStore(self.betas), buckets=tuple(tr["buckets"]),
        )
        for rows in tr["buckets"]:  # warm-up: one flush per bucket
            self.server.submit(self.pool[:rows])
            self.server.flush()
        self.rng = np.random.default_rng(self.seed)

    def schedule(self, seconds: float):
        """Due times (s from the window's start), sizes and pool offsets
        of the window's round(rate * seconds) requests. The gaps are the
        fixed quantiles of an exponential at the rate, so the last one is
        due near ``seconds`` whatever the seed."""
        tr = self.traffic
        n = max(1, round(self.rate * seconds))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / self.rate
        lo, hi = math.log(tr["rows_min"]), math.log(tr["rows_max"] + 1)
        sizes = np.floor(np.exp(lo + (hi - lo) * q)).astype(np.int64)
        due = np.cumsum(self.rng.permutation(gaps))
        sizes = self.rng.permutation(sizes)
        starts = self.rng.integers(0, tr["pool_rows"] - sizes + 1)
        return due, sizes, starts

    def window(self, seconds, clock=time.perf_counter):
        due, sizes, starts = self.schedule(seconds)
        n = len(due)
        sample = set(self.rng.choice(n, min(n, self.traffic["check_sample"] - 1),
                                     replace=False).tolist())
        sample.add(int(np.argmax(sizes)))
        server, m0 = self.server, dict(self.server.metrics)
        done = np.full(n, np.nan)
        late = np.empty(n)
        index, kept = {}, {}
        i = 0
        t0 = clock()
        while True:
            now = clock() - t0
            with harness.span("submit"):
                while i < n and due[i] <= now:
                    uid = server.submit(self.pool[starts[i]:starts[i] + sizes[i]])
                    late[i] = clock() - t0 - due[i]
                    index[uid] = i
                    i += 1
            if index:
                with harness.span("flush"):
                    responses = server.flush()
                t = clock() - t0
                for r in responses:
                    j = index.pop(r.uid)
                    done[j] = t
                    if j in sample:
                        kept[j] = r
            elif i >= n:
                break
            else:
                with harness.span("wait_arrival"):
                    time.sleep(max(0.0, due[i] - (clock() - t0)))
        elapsed = clock() - t0
        self.kept = [(starts[j], sizes[j], r) for j, r in sorted(kept.items())]
        latency = done - due
        mets = {k: server.metrics[k] - m0[k] for k in
                ("rows", "padded_rows", "batches", "rejected")}
        cfg = self.cfg
        counters = {
            "requests_due": n,
            "requests_split": int(np.sum(sizes > self.traffic["buckets"][-1])),
            **mets,
            "useful_flops": work.predict_terms(
                mets["rows"], mets["batches"], cfg["D"], cfg["L"], cfg["M"]
            )[0],
            "late_p99_ms": 1e3 * float(np.quantile(late, 0.99)) if n else 0.0,
            "latency_p50_ms": 1e3 * loops.latency_quantile(latency, 0.5),
        }
        return harness.Window(
            end_to_end={"serve_p99_ms": 1e3 * loops.latency_quantile(latency, 0.99)},
            counters=counters,
            attempted=n,
            failed=int(np.sum(np.isnan(done))),
            seconds=elapsed,
        )

    def release(self):
        self.server = None

    def outputs(self):
        """The sampled responses: (rows asked for, node, rows served)."""
        return [
            (self.pool[s:s + n], r.node, np.asarray(r.y)) for s, n, r in self.kept
        ]

    def control_outputs(self):
        """The reference's rows a precision step lower, for the same
        requests and nodes."""
        return [
            (x, node, np.asarray(reference.predict(
                jnp.asarray(x), self.W, self.b, self.betas[node],
                activation=self.cfg["activation"], precision="high",
            )))
            for x, node, _ in self.outputs()
        ]

    def compare(self, out):
        """The worst served request's relative error against float64."""
        W, b = np.asarray(self.W), np.asarray(self.b)
        betas = np.asarray(self.betas)
        worst = 0.0
        for x, node, y in out:
            want = reference.numpy_predict(W, b, x, betas[node], self.cfg["activation"])
            worst = max(worst, reference.rel_err(y, want))
        return {"rows_rel": worst}

    def check(self):
        return self.compare(self.outputs())
