"""The benchmark's FLOP and byte counters and its window rules."""

import math

import pytest
from bench_tiny import REPO  # noqa: F401  (puts the repository on sys.path)

from bench import loops, work


def test_stats_terms_by_hand():
    # N=2 rows, D=3, L=4, M=5: features 2*2*3*4, symmetric P 2*4*5,
    # Q 2*2*4*5; bytes of X, W, b, T, P and Q in float32
    flops, nbytes = work.stats_terms(2, 3, 4, 5)
    assert flops == 48 + 40 + 80
    assert nbytes == 4 * (6 + 12 + 4 + 10 + 16 + 20)


def test_round_and_gossip_terms_by_hand():
    # V=3 nodes, 4 directed edges, L=2, M=1
    assert work.round_flops(3, 4, 2, 1) == 2 * 4 * 2 + 2 * 3 * 4
    t = work.gossip_round_terms(3, 2, 2, 1)
    assert t["flops"] == 2 * 3 * 2 * 2 + 2 * 3 * 4
    assert t["hbm_bytes"] == 4 * (2 * 3 * 2 + 3 * 4) + 2 * 4 * 3 * 2
    dense = work.gossip_round_terms(3, 2, 2, 1, dense=True)
    assert dense["hbm_bytes"] == 4 * (2 * 3 * 2 + 3 * 4) + 4 * 9


def test_omega_woodbury_predict_by_hand():
    assert work.omega_flops(3, 2) == 9 + 54 + 36
    # L=2, M=1, dN=1: 4*4 + 4*2 + 1 + 2*2 + 2*4
    assert work.woodbury_flops(2, 1, 1) == 16 + 8 + 1 + 4 + 8
    flops, nbytes = work.predict_terms(10, 2, 3, 4, 5)
    assert flops == 10 * (2 * 3 * 4 + 2 * 4 * 5)
    assert nbytes == 4 * (10 * 8 + 2 * (12 + 4 + 20))


def test_least_seconds_takes_the_slower_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000.0, 50.0, peak) == 10.0
    assert work.least_seconds(100.0, 50.0, peak) == 5.0


def test_converge_stops_at_eps_in_blocks_of_k():
    # the residual halves every block: 1, .5, .25, .125 <= .2 after 3 blocks
    state, rounds, reached = loops.converge(
        1.0, lambda s: s / 2, lambda s: s, eps=0.2, K=50, max_rounds=10_000
    )
    assert (state, rounds, reached) == (0.125, 150, True)


def test_converge_checks_before_the_first_block():
    state, rounds, reached = loops.converge(
        0.1, lambda s: s / 2, lambda s: s, eps=0.2, K=50, max_rounds=100
    )
    assert (rounds, reached) == (0, True)


def test_converge_gives_up_at_the_cap():
    state, rounds, reached = loops.converge(
        1.0, lambda s: s, lambda s: s, eps=0.2, K=50, max_rounds=200
    )
    assert (rounds, reached) == (200, False)


def test_window_closes_at_the_end_of_the_first_job_past_the_length():
    t = [0.0]

    def clock():
        return t[0]

    def job():
        t[0] += 3.0
        return t[0]

    results, elapsed = loops.back_to_back(job, 10.0, clock=clock)
    # jobs end at 3, 6, 9, 12: the one ending at 12 closes the window
    assert results == [3.0, 6.0, 9.0, 12.0]
    assert elapsed == 12.0


def test_latency_quantile_counts_a_missing_answer_as_infinite():
    lat = [0.001 * i for i in range(1, 100)] + [None]
    assert loops.latency_quantile(lat, 0.99) == pytest.approx(0.099)
    assert math.isinf(loops.latency_quantile(lat[:-2] + [None, float("nan")], 0.99))
    assert loops.latency_quantile([0.5], 0.99) == 0.5


def test_key_keeps_every_bit_of_the_seed():
    import jax

    a, b = loops.key(5), loops.key(2**33 + 5)
    assert not bool((jax.random.key_data(a) == jax.random.key_data(b)).all())


def test_every_seed_learns_the_same_rows_in_its_own_order():
    """The deployment's rows are fixed by its data_seed; the run's seed
    only orders each node's rows, so every seed does the same work."""
    import jax
    import numpy as np

    from bench.network import Network

    cfg = {"V": 3, "Ni": 8, "D": 5, "L": 4, "M": 2, "C": "1/(V*Ni)",
           "activation": "sigmoid", "graph": {"kind": "ring", "seed": 0},
           "gamma_safety": 0.9, "engine": "simulated", "data_seed": 0}
    net = Network(cfg, jax.devices()[:1])
    (X1, T1), (X2, T2) = (net.data(0, 8, loops.key(s)) for s in (1, 2))
    X1, T1, X2, T2 = map(np.asarray, (X1, T1, X2, T2))
    assert not np.array_equal(X1, X2)
    for v in range(3):
        a = np.concatenate([X1[v], T1[v]], axis=1)
        b = np.concatenate([X2[v], T2[v]], axis=1)
        assert np.array_equal(a[np.lexsort(a.T)], b[np.lexsort(b.T)])
    X3, _ = map(np.asarray, net.data(1, 8, loops.key(1)))
    assert not np.array_equal(np.sort(X1, axis=None), np.sort(X3, axis=None))
