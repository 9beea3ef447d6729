"""The rules a measured window follows, apart from what they drive.

* ``converge``: blocks of K consensus rounds until the consensus
  residual is at most epsilon (or a cap on rounds is reached, which
  fails the job), as one device-side loop;
* ``back_to_back``: jobs one after another; the window closes at the end
  of the first job that ends after the window's length;
* ``latency_quantile``: a tail over every request due in the window,
  where a request that was never answered counts as infinitely late.
"""

from __future__ import annotations

import math
import time

import numpy as np

from bench.harness import span


def key(seed: int):
    """A PRNG key from a seed of any size (``jax.random.key`` keeps only
    the low 32 bits)."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def converge(state, run_block, residual, *, eps, K, max_rounds, rounds=0):
    """Returns (state, rounds run, whether the residual reached eps).

    Traceable: under ``jax.jit`` the whole loop, its residual checks
    included, is one program on the device, so that the host waits once
    for it and not once a block, and a stall of the host costs the
    device nothing while the loop runs."""
    import jax
    import jax.numpy as jnp

    def more(carry):
        s, r = carry
        return (residual(s) > eps) & (r < max_rounds)

    def block(carry):
        s, r = carry
        return run_block(s), r + K

    state, rounds = jax.lax.while_loop(more, block, (state, jnp.int32(rounds)))
    return state, rounds, residual(state) <= eps


def back_to_back(job, seconds: float, clock=time.perf_counter):
    """Runs ``job()`` until one ends after ``seconds``; returns the
    results and the seconds from the first start to the last end."""
    t0 = clock()
    results = []
    while True:
        results.append(job())
        elapsed = clock() - t0
        if elapsed >= seconds:
            return results, elapsed


def latency_quantile(latencies, q: float) -> float:
    """The q-quantile (0..1) of latencies; ``None`` or NaN (a request
    that never came back) counts as infinite."""
    lat = np.array(
        [math.inf if x is None or x != x else x for x in latencies], np.float64
    )
    if lat.size == 0:
        return math.inf
    lat.sort()
    # the smallest value that at least a q share of requests meets
    return float(lat[min(lat.size - 1, math.ceil(q * lat.size) - 1)])
