"""Vertical (column-partitioned) DC-ELM: assembly, parity, serving.

The load-bearing invariant: blocked float matmul is not associative,
so ``VerticalFeatureMap`` owns the canonical contraction (left fold in
node order). Both the distributed reduction and the centralized stats
plane run that same fold, which is what makes the bitwise-in-f64
acceptance criterion well-defined.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    consensus,
    dc_elm,
    engine,
    online,
    stats as stats_lib,
    vertical,
)
from repro.core.consensus import FaultModel, NodeCrash
from repro.core.features import make_random_features
from repro.core.secure import SecureAggregationSpec
from repro.core.vertical import (
    ColumnPartition,
    SpanningTree,
    VerticalFeatureMap,
    make_vertical_map,
)
from repro.kernels import elm_stats_ops
from repro.kernels.elm_stats import elm_preact_stats_pallas
from repro.kernels.elm_stats_ref import (
    preact_stats_reference,
    preact_stats_scan,
)
from repro.serving import BetaStore, ELMServer


def _problem(N, D, L, M, V, *, seed=0, activation="tanh"):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    T = jnp.asarray(rng.standard_normal((N, M)), jnp.float32)
    fmap = make_vertical_map(
        jax.random.key(seed), D, L, V, activation=activation
    )
    return X, T, fmap


# ---------------------------------------------------------------------------
# Partition / feature-map plumbing
# ---------------------------------------------------------------------------


def test_partition_even_and_from_widths():
    p = ColumnPartition.even(10, 4)
    assert p.in_dim == 10 and p.num_nodes == 4
    assert sum(p.widths) == 10 and max(p.widths) - min(p.widths) <= 1
    q = ColumnPartition.from_widths([3, 3, 2, 2])
    assert q.bounds == p.bounds


def test_partition_validation():
    with pytest.raises(ValueError):
        ColumnPartition((0, 5, 3, 8))  # not increasing
    with pytest.raises(ValueError):
        ColumnPartition((1, 5))  # must start at 0


def test_make_vertical_map_custom_partition():
    part = ColumnPartition.from_widths([5, 4, 6, 3])
    fmap = vertical.make_vertical_map(
        jax.random.key(0), 18, 8, 4, partition=part
    )
    assert fmap.partition is part
    assert [s.shape[1] for s in part.split(jnp.zeros((3, 18)))] == [
        5, 4, 6, 3,
    ]
    with pytest.raises(ValueError, match="partition covers"):
        vertical.make_vertical_map(
            jax.random.key(0), 18, 8, 3, partition=part
        )
    with pytest.raises(ValueError, match="partition covers"):
        vertical.make_vertical_map(
            jax.random.key(0), 20, 8, 4, partition=part
        )


def test_split_concat_roundtrip():
    X, _, fmap = _problem(20, 9, 8, 1, 3)
    parts = fmap.partition.split(X)
    assert len(parts) == 3
    np.testing.assert_array_equal(np.concatenate(parts, axis=1), X)


def test_vertical_map_matches_canonical_fold():
    """__call__ == g(left-fold of partials + b), by construction."""
    X, _, fmap = _problem(40, 7, 12, 1, 3)
    parts = fmap.partition.split(X)
    Z = VerticalFeatureMap.assemble(
        [fmap.partial_preactivation(i, x) for i, x in enumerate(parts)]
    )
    np.testing.assert_array_equal(np.asarray(fmap(X)),
                                  np.asarray(jnp.tanh(Z + fmap.bias)))


def test_from_shards_roundtrip():
    X, _, fmap = _problem(16, 6, 10, 1, 2)
    shards = [fmap.weight_shard(i) for i in range(2)]
    rebuilt = VerticalFeatureMap.from_shards(
        shards, fmap.bias, fmap.activation
    )
    np.testing.assert_array_equal(np.asarray(rebuilt(X)),
                                  np.asarray(fmap(X)))


def test_rbf_rejected():
    rbf = make_random_features(jax.random.key(0), 6, 8, "rbf")
    with pytest.raises((TypeError, ValueError)):
        VerticalFeatureMap(rbf, ColumnPartition.even(6, 2))


def test_spanning_tree_bfs():
    t = SpanningTree.bfs(consensus.line(5), root=0)
    assert t.depth == (0, 1, 2, 3, 4)
    assert t.parent[4] == 3
    ring = SpanningTree.bfs(consensus.ring(6), root=0)
    assert max(ring.depth) == 3
    # disconnected graph raises
    adj = np.zeros((4, 4))
    adj[0, 1] = adj[1, 0] = 1.0
    with pytest.raises(ValueError):
        SpanningTree.bfs(consensus.Graph(adjacency=adj))


# ---------------------------------------------------------------------------
# Bitwise parity: distributed assembly == centralized stats plane
# ---------------------------------------------------------------------------


def test_vertical_stats_bitwise_f64_vs_centralized():
    """Acceptance: assembled (P, Q) from column-sliced nodes matches
    the centralized horizontal stats plane bitwise in f64."""
    with jax.enable_x64():
        rng = np.random.default_rng(1)
        N, D, L, M, V = 150, 11, 24, 2, 4
        X = jnp.asarray(rng.standard_normal((N, D)), jnp.float64)
        T = jnp.asarray(rng.standard_normal((N, M)), jnp.float64)
        fmap = make_vertical_map(
            jax.random.key(1), D, L, V, dtype=jnp.float64
        )
        for g in (consensus.ring(V), consensus.line(V),
                  consensus.complete(V)):
            s, rep = vertical.vertical_stats(
                fmap.partition.split(X), T, fmap, graph=g,
                dtype=jnp.float64,
            )
            P0, Q0 = stats_lib.raw_moments(X, T, fmap, dtype=jnp.float64)
            assert s.P.dtype == jnp.float64
            np.testing.assert_array_equal(np.asarray(s.P), np.asarray(P0))
            np.testing.assert_array_equal(np.asarray(s.Q), np.asarray(Q0))
            assert rep.delivered == tuple(range(V))


def test_vertical_stats_f32_and_bf16_pinned_tol():
    X, T, fmap = _problem(128, 8, 20, 2, 3, seed=2)
    s, _ = vertical.vertical_stats(fmap.partition.split(X), T, fmap)
    P0, Q0 = stats_lib.raw_moments(X, T, fmap)
    np.testing.assert_allclose(s.P, P0, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s.Q, Q0, rtol=1e-6, atol=1e-6)
    # bf16 column slices: pinned at bf16 grid tolerance
    from repro.core import features

    base16 = features.RandomFeatureMap(
        weights=fmap.base.weights.astype(jnp.bfloat16),
        bias=fmap.base.bias.astype(jnp.bfloat16),
        activation=fmap.activation,
    )
    fb = VerticalFeatureMap(base=base16, partition=fmap.partition)
    sb, _ = vertical.vertical_stats(
        fb.partition.split(X.astype(jnp.bfloat16)), T, fb
    )
    np.testing.assert_allclose(sb.P, P0, rtol=0.1, atol=0.2)


def test_vertical_stats_secure_pinned_tol():
    X, T, fmap = _problem(100, 9, 16, 1, 3, seed=3)
    spec = SecureAggregationSpec(seed=5)
    s, rep = vertical.vertical_stats(
        fmap.partition.split(X), T, fmap, secure=spec
    )
    P0, Q0 = stats_lib.raw_moments(X, T, fmap)
    # fixed-point grid on Z then one activation: small pinned tolerance
    np.testing.assert_allclose(s.P, P0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s.Q, Q0, rtol=1e-5, atol=1e-5)
    assert rep.wire.bytes_on_wire > 0


def test_wire_accounting_secure_vs_clear():
    """Secure payloads are constant-width (8 B/value): on a deep tree
    they beat clear convergecast, whose messages grow toward the root."""
    rng = np.random.default_rng(4)
    V, N, L = 6, 64, 12
    partials = [rng.standard_normal((N, L)) for _ in range(V)]
    g = consensus.line(V)
    _, clear = vertical.reduce_partials(partials, g)
    _, sec = vertical.reduce_partials(
        partials, g, secure=SecureAggregationSpec(seed=0)
    )
    assert sec.wire.bytes_on_wire < clear.wire.bytes_on_wire
    # the baseline prices every origin payload at f64 clear convergecast
    assert clear.wire.bytes_uncompressed >= clear.wire.bytes_on_wire
    assert sec.wire.bytes_uncompressed == clear.wire.bytes_uncompressed
    for rep in (clear, sec):
        assert int(np.sum(rep.wire.per_round_bytes)) == rep.wire.bytes_on_wire


def test_dropped_node_degrades_gracefully():
    X, T, fmap = _problem(80, 8, 14, 1, 4, seed=5)
    g = consensus.line(4)
    fm = FaultModel(
        graph=g, crashes=(NodeCrash(node=2, start=1, duration=9),)
    )
    s, rep = vertical.vertical_stats(
        fmap.partition.split(X), T, fmap, graph=g, faults=fm
    )
    assert set(rep.delivered) < set(range(4))
    # the assembled stats are those of the surviving columns' fold
    parts = fmap.partition.split(X)
    Z = VerticalFeatureMap.assemble(
        [fmap.partial_preactivation(i, parts[i]) for i in rep.delivered]
    )
    H = jnp.tanh(Z + fmap.bias)
    P0, Q0 = stats_lib.hidden_moments(H, T)
    np.testing.assert_allclose(s.P, P0, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Training: init at optimum, streaming, entry points
# ---------------------------------------------------------------------------


def test_vertical_train_matches_centralized_ridge():
    X, T, fmap = _problem(120, 10, 18, 2, 3, seed=6)
    beta, s, _ = vertical.vertical_train(
        fmap.partition.split(X), T, fmap, C=10.0
    )
    H = fmap(X)
    beta0 = stats_lib.ridge_solve_moments(
        *stats_lib.hidden_moments(H, T), C=10.0
    )
    np.testing.assert_allclose(beta, beta0, rtol=1e-4, atol=1e-5)


def test_simulate_init_seeds_all_nodes_at_optimum():
    X, T, fmap = _problem(90, 6, 12, 1, 3, seed=7)
    g = consensus.ring(3)
    state, s, _ = dc_elm.simulate_init_vertical(
        fmap.partition.split(X), T, fmap, 10.0, g
    )
    beta, _, _ = vertical.vertical_train(
        fmap.partition.split(X), T, fmap, C=10.0, graph=g
    )
    assert state.betas.shape[0] == 3
    np.testing.assert_allclose(
        state.betas, jnp.broadcast_to(beta, state.betas.shape),
        rtol=1e-4, atol=1e-5,
    )
    # consensus from the optimum stays at the optimum
    gamma = 0.5 * g.gamma_upper_bound()
    out, _ = dc_elm.simulate_run(state, g, gamma, 10.0, 5)
    np.testing.assert_allclose(out.betas, state.betas, rtol=1e-4, atol=1e-4)


def test_stream_chunk_matches_retrain():
    X, T, fmap = _problem(100, 9, 15, 1, 3, seed=8)
    g = consensus.ring(3)
    eng = engine.simulated_dc_elm(g, 10.0)
    eng = engine.with_secure_aggregation(eng)
    assert eng.secure is not None
    st, _, _ = vertical.stream_init(eng, fmap.partition.split(X), T, fmap,
                                    graph=g)
    rng = np.random.default_rng(9)
    Xn = jnp.asarray(rng.standard_normal((30, 9)), jnp.float32)
    Tn = jnp.asarray(rng.standard_normal((30, 1)), jnp.float32)
    (st2, _), rep = vertical.stream_chunk(
        eng, st, fmap.partition.split(Xn), Tn, fmap,
        gamma=0.1, num_iters=2, graph=g,
    )
    Xall = jnp.concatenate([X, Xn])
    Tall = jnp.concatenate([T, Tn])
    beta_all, _, _ = vertical.vertical_train(
        fmap.partition.split(Xall), Tall, fmap, C=10.0, graph=g
    )
    np.testing.assert_allclose(st2.betas[0], beta_all, rtol=1e-3, atol=1e-4)
    # removing the chunk restores the original optimum
    (st3, _), _ = vertical.stream_chunk(
        eng, st2, fmap.partition.split(Xn), Tn, fmap,
        gamma=0.1, num_iters=2, graph=g, remove=True,
    )
    beta0, _, _ = vertical.vertical_train(
        fmap.partition.split(X), T, fmap, C=10.0, graph=g
    )
    np.testing.assert_allclose(st3.betas[0], beta0, rtol=1e-3, atol=1e-4)


def test_online_vertical_chunk_node_local():
    X, T, fmap = _problem(80, 8, 10, 1, 2, seed=10)
    g = consensus.complete(2)
    state, s, _ = vertical.simulate_init(
        fmap.partition.split(X), T, fmap, 10.0, g
    )
    ns = online.OnlineNodeState(
        omega=state.omegas[0], Q=(s.Q / 2).astype(state.omegas.dtype)
    )
    rng = np.random.default_rng(11)
    Xn = jnp.asarray(rng.standard_normal((20, 8)), jnp.float32)
    Tn = jnp.asarray(rng.standard_normal((20, 1)), jnp.float32)
    ns2, rep = online.vertical_chunk(
        ns, fmap.partition.split(Xn), Tn, fmap, graph=g
    )
    beta_all, _, _ = vertical.vertical_train(
        fmap.partition.split(jnp.concatenate([X, Xn])),
        jnp.concatenate([T, Tn]), fmap, C=10.0, graph=g,
    )
    np.testing.assert_allclose(ns2.beta, beta_all, rtol=1e-3, atol=1e-4)


def test_engine_secure_field_survives_wrappers():
    g = consensus.ring(4)
    eng = engine.simulated_dc_elm(g, 10.0)
    eng = engine.with_secure_aggregation(eng, 42)
    assert eng.secure.seed == 42
    from repro.core.compression import CompressionSpec

    eng2 = engine.with_compression(eng, CompressionSpec(mode="bf16"))
    assert eng2.secure.seed == 42
    fm = FaultModel(graph=g, edge_drop_prob=0.1)
    eng3 = engine.with_faults(eng2, fm, 4)
    assert eng3.secure.seed == 42


# ---------------------------------------------------------------------------
# Kernel plane: fused preactivation moments
# ---------------------------------------------------------------------------


@pytest.mark.interpret
@pytest.mark.parametrize("activation", ["sigmoid", "tanh", "relu"])
def test_preact_kernel_matches_oracle(activation):
    rng = np.random.default_rng(12)
    Z = jnp.asarray(rng.standard_normal((100, 33)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((33,)), jnp.float32)
    T = jnp.asarray(rng.standard_normal((100, 3)), jnp.float32)
    P0, Q0 = preact_stats_reference(Z, b, T, activation=activation)
    P1, Q1 = elm_preact_stats_pallas(
        Z, b, T, activation=activation, interpret=True,
        block_l=16, block_n=32,
    )
    np.testing.assert_allclose(P1, P0, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(Q1, Q0, rtol=2e-3, atol=2e-3)
    P2, Q2 = preact_stats_scan(Z, b, T, activation=activation, chunk=32)
    np.testing.assert_allclose(P2, P0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(Q2, Q0, rtol=1e-5, atol=1e-5)


@pytest.mark.interpret
@pytest.mark.parametrize(
    "N,L,M", [(64, 32, 2), (33, 7, 5), (130, 100, 1)]
)
def test_preact_kernel_ragged_shapes(N, L, M):
    rng = np.random.default_rng(13)
    Z = jnp.asarray(rng.standard_normal((N, L)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((L,)), jnp.float32)
    T = jnp.asarray(rng.standard_normal((N, M)), jnp.float32)
    P0, Q0 = preact_stats_reference(Z, b, T, activation="sigmoid")
    P1, Q1 = elm_preact_stats_pallas(
        Z, b, T, interpret=True, block_l=16, block_n=32
    )
    np.testing.assert_allclose(P1, P0, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(Q1, Q0, rtol=2e-3, atol=2e-3)


def test_preact_dispatch_and_rbf_rejection():
    rng = np.random.default_rng(14)
    Z = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16,)), jnp.float32)
    T = jnp.asarray(rng.standard_normal((64, 2)), jnp.float32)
    P0, Q0 = preact_stats_reference(Z, b, T, activation="sigmoid")
    for use_kernel in (False, True):
        P, Q = elm_stats_ops.fused_preact_moments(
            Z, b, T, use_kernel=use_kernel
        )
        np.testing.assert_allclose(P, P0, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(Q, Q0, rtol=2e-3, atol=2e-3)
    with pytest.raises(ValueError, match="rbf"):
        elm_stats_ops.fused_preact_moments(Z, b, T, activation="rbf")


# ---------------------------------------------------------------------------
# Serving a vertically assembled model
# ---------------------------------------------------------------------------


def test_elm_server_serves_vertical_map():
    """VerticalFeatureMap takes the materialize path (not fusable) and
    serves through the bucketed batcher unchanged."""
    X, T, fmap = _problem(60, 8, 12, 2, 3, seed=15)
    beta, _, _ = vertical.vertical_train(
        fmap.partition.split(X), T, fmap, C=10.0
    )
    assert stats_lib.fusable_params(fmap) is None
    srv = ELMServer(fmap, BetaStore(beta[None]), buckets=(16, 64))
    rng = np.random.default_rng(16)
    q = rng.standard_normal((10, 8)).astype(np.float32)
    y = srv.predict(q, node=0)
    ref = np.asarray(fmap(jnp.asarray(q)) @ beta)
    np.testing.assert_allclose(y, ref, rtol=2e-5, atol=2e-5)
