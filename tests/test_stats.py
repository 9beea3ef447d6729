"""The statistics plane: fused kernel parity, chunked accumulation,
Cholesky finalization, and the feature-map satellites."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dc_elm, elm, engine, features, online, stats
from repro.kernels import elm_stats, gram_ops, vmem
from repro.kernels.elm_stats import elm_stats_pallas
from repro.kernels.elm_stats_ref import elm_stats_scan, hidden_reference

ALL_ACTIVATIONS = ["sigmoid", "tanh", "relu", "sin", "identity", "rbf"]


def _problem(N, D, L, M, activation="sigmoid", dtype=jnp.float32, seed=0):
    fmap = features.make_random_features(jax.random.key(seed), D, L, activation)
    ks = jax.random.split(jax.random.key(seed + 1), 2)
    X = jax.random.normal(ks[0], (N, D), dtype)
    T = jax.random.normal(ks[1], (N, M), dtype)
    return fmap, X, T


# ---------------------------------------------------------------------------
# Fused kernel vs the materialize-then-gram oracle, in both arms
# ---------------------------------------------------------------------------


def _blocked_arm(monkeypatch):
    """Send ``elm_stats_pallas`` to its L-blocked arm: a VMEM budget no
    whole-L resident set fits. The arm is fixed when a shape is first
    traced, so jit caches are cleared on the way in and out."""
    monkeypatch.setattr(elm_stats, "vmem_budget", lambda: 0)
    jax.clear_caches()


@pytest.fixture(params=["whole_l", "blocked"])
def arm(request, monkeypatch):
    """The stats kernel's arm a test runs: whole-L (every shape here
    fits the real budget) or L-blocked."""
    if request.param == "blocked":
        _blocked_arm(monkeypatch)
        yield request.param
        jax.clear_caches()
    else:
        yield request.param


@pytest.mark.interpret
@pytest.mark.parametrize("activation", ALL_ACTIVATIONS)
def test_fused_kernel_matches_oracle_all_activations(arm, activation):
    fmap, X, T = _problem(100, 5, 33, 3, activation)
    W, b, act = stats.fusable_params(fmap)
    P1, Q1 = elm_stats_pallas(
        X, W, b, T, activation=act, interpret=True, block_l=16, block_n=32
    )
    P0, Q0 = gram_ops.local_elm_stats(fmap(X), T)
    np.testing.assert_allclose(P1, P0, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(Q1, Q0, rtol=2e-3, atol=2e-3)


@pytest.mark.interpret
@pytest.mark.parametrize(
    "N,D,L,M", [(64, 4, 32, 2), (300, 7, 100, 1), (33, 3, 7, 5),
                (128, 16, 64, 8)]
)
def test_fused_kernel_shape_sweep_ragged(arm, N, D, L, M):
    """Ragged N/L/M tails must mask, not pollute (g(0) != 0!)."""
    fmap, X, T = _problem(N, D, L, M)
    W, b, act = stats.fusable_params(fmap)
    P1, Q1 = elm_stats_pallas(
        X, W, b, T, activation=act, interpret=True, block_l=16, block_n=32
    )
    P0, Q0 = gram_ops.local_elm_stats(fmap(X), T)
    assert P1.dtype == Q1.dtype == jnp.float32
    np.testing.assert_allclose(P1, P0, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(Q1, Q0, rtol=2e-3, atol=2e-3)


@pytest.mark.interpret
@pytest.mark.parametrize("activation", ["sigmoid", "rbf"])
def test_fused_kernel_bf16_operands(arm, activation):
    fmap, X, T = _problem(128, 6, 40, 2, activation)
    W, b, act = stats.fusable_params(fmap)
    Xb, Tb = X.astype(jnp.bfloat16), T.astype(jnp.bfloat16)
    P1, Q1 = elm_stats_pallas(
        Xb, W, b, Tb, activation=act, interpret=True, block_l=16, block_n=32
    )
    # oracle on the same bf16 operands (materialized bf16 H, f32 acc)
    Hb = hidden_reference(
        Xb, W.astype(jnp.bfloat16), b, act
    ).astype(jnp.bfloat16)
    P0, Q0 = gram_ops.local_elm_stats(Hb, Tb)
    assert P1.dtype == jnp.float32
    np.testing.assert_allclose(P1, P0, rtol=5e-2, atol=5e-2 * 128**0.5)
    np.testing.assert_allclose(Q1, Q0, rtol=5e-2, atol=5e-2 * 128**0.5)


@pytest.mark.interpret
def test_fused_kernel_keeps_f32_target_precision(arm):
    """bf16 features + f32 targets with a large offset: the kernel must
    not quantize T down to bf16 — pinned against the scan path, which
    keeps T f32."""
    fmap, X, T = _problem(96, 5, 24, 2, seed=11)
    W, b, act = stats.fusable_params(fmap)
    Xb = X.astype(jnp.bfloat16)
    T_off = T + 1000.0  # bf16 would round this to ~4 decimal digits
    P1, Q1 = elm_stats_pallas(
        Xb, W, b, T_off, activation=act, interpret=True,
        block_l=16, block_n=32,
    )
    P2, Q2 = elm_stats_scan(
        Xb, W.astype(jnp.bfloat16), b, T_off, activation=act, chunk=32
    )
    np.testing.assert_allclose(Q1, Q2, rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(P1, P2, rtol=1e-5, atol=1e-4)


@pytest.mark.interpret
def test_fused_kernel_symmetric_matches_full(arm):
    fmap, X, T = _problem(96, 5, 48, 2)
    W, b, act = stats.fusable_params(fmap)
    kw = dict(activation=act, interpret=True, block_l=16, block_n=32)
    P_sym, Q_sym = elm_stats_pallas(X, W, b, T, symmetric=True, **kw)
    P_full, Q_full = elm_stats_pallas(X, W, b, T, symmetric=False, **kw)
    np.testing.assert_allclose(P_sym, P_full, rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(Q_sym, Q_full)


@pytest.mark.interpret
@pytest.mark.parametrize("activation,N,D,L,M,symmetric", [
    ("sigmoid", 100, 5, 33, 3, True),
    ("rbf", 100, 5, 33, 3, True),
    ("tanh", 300, 7, 100, 1, True),
    ("relu", 128, 16, 64, 8, False),
])
def test_stats_arms_agree(monkeypatch, activation, N, D, L, M, symmetric):
    """The whole-L arm against the L-blocked one: each element sums the
    same rows in the same order, so the two differ only by the f32
    rounding of differently shaped CPU dots (none at some shapes)."""
    fmap, X, T = _problem(N, D, L, M, activation)
    W, b, act = stats.fusable_params(fmap)
    kw = dict(activation=act, interpret=True, block_l=16, block_n=32,
              symmetric=symmetric)
    P1, Q1 = elm_stats_pallas(X, W, b, T, **kw)
    _blocked_arm(monkeypatch)
    try:
        P2, Q2 = elm_stats_pallas(X, W, b, T, **kw)
    finally:
        jax.clear_caches()
    np.testing.assert_allclose(P1, P2, rtol=0, atol=1e-6 * np.abs(P2).max())
    np.testing.assert_allclose(Q1, Q2, rtol=0, atol=1e-6 * np.abs(Q2).max())


def _stats_kernel_names(fn, *args):
    """Names of the Pallas calls ``fn`` makes at these abstract shapes
    (traced, not compiled)."""

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield str(eqn.params["name"])
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)

    shapes = [jax.ShapeDtypeStruct(shape, jnp.float32) for shape in args]
    return set(walk(jax.jit(fn).trace(*shapes).jaxpr.jaxpr))


_nodes = jax.vmap(elm_stats_pallas, in_axes=(0, None, None, 0))

#: the stats kernel's shapes in each benchmark cell, and L = 2048
ARM_SHAPES = {
    "mnist64": (_nodes, (64, 16384, 784), (784, 1024), (1024,),
                (64, 16384, 10)),
    "mnist8m-silo4": (_nodes, (1, 1 << 20, 784), (784, 1024), (1024,),
                      (1, 1 << 20, 10)),
    "rgg1024": (_nodes, (1024, 256, 784), (784, 256), (256,),
                (1024, 256, 10)),
    "wide": (elm_stats_pallas, (32768, 784), (784, 2048), (2048,),
             (32768, 10)),
}


@pytest.mark.parametrize("small_budget", [False, True])
@pytest.mark.parametrize("shape", sorted(ARM_SHAPES))
def test_stats_arm_chooser(monkeypatch, shape, small_budget):
    """Under v5e's budget every cell's shape, and L = 2048, takes the
    whole-L arm; a budget too small for its resident set takes the
    L-blocked one."""
    budget = vmem._V5E_VMEM_BYTES // 2
    if small_budget:
        budget = 16 * 2**20 if shape == "wide" else 2**20
    monkeypatch.setattr(elm_stats, "vmem_budget", lambda: budget)
    jax.clear_caches()
    try:
        names = _stats_kernel_names(*ARM_SHAPES[shape])
    finally:
        jax.clear_caches()
    arm = "elm_stats_pallas" if small_budget else "elm_stats_pallas_whole_l"
    assert names == {arm}


def test_streaming_scan_matches_oracle():
    fmap, X, T = _problem(200, 6, 31, 3)
    W, b, act = stats.fusable_params(fmap)
    P1, Q1 = elm_stats_scan(X, W, b, T, activation=act, chunk=64)
    P0, Q0 = gram_ops.local_elm_stats(fmap(X), T)
    np.testing.assert_allclose(P1, P0, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(Q1, Q0, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Chunked accumulation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("N", [128, 150])  # exact 4x and ragged-tail stream
def test_chunked_accumulate_bitwise_matches_one_shot(N):
    """An N >= 4x chunk-size stream through SufficientStats.accumulate
    reproduces the one-shot fused result *bitwise* (same f32
    accumulation order when chunk == block_n)."""
    chunk = 32
    fmap, X, T = _problem(N, 4, 20, 2, seed=3)
    kw = dict(use_kernel=True, block_n=chunk, block_l=16)
    one = stats.from_raw(X, T, fmap, **kw)
    s = stats.SufficientStats.zero(20, 2)
    for i in range(0, N, chunk):
        s = s.accumulate(X[i:i + chunk], T[i:i + chunk], fmap, **kw)
    np.testing.assert_array_equal(np.asarray(one.P), np.asarray(s.P))
    np.testing.assert_array_equal(np.asarray(one.Q), np.asarray(s.Q))
    assert float(s.count) == N
    np.testing.assert_allclose(s.t_sq, one.t_sq, rtol=1e-6)


def test_from_hidden_matches_from_raw():
    fmap, X, T = _problem(70, 5, 14, 2, seed=9)
    via_h = stats.from_hidden(fmap(X), T)
    via_raw = stats.from_raw(X, T, fmap)
    np.testing.assert_allclose(via_h.P, via_raw.P, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(via_h.Q, via_raw.Q, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(via_h.t_sq, via_raw.t_sq, rtol=1e-6)
    assert float(via_h.count) == 70


def test_bf16_features_accumulate_f32():
    """bf16 operands must not produce bf16 moments (dtype-policy pin)."""
    fmap, X, T = _problem(60, 4, 12, 2, seed=10)
    Hb = fmap(X).astype(jnp.bfloat16)
    P_, Q_ = dc_elm.local_stats(Hb, T)
    assert P_.dtype == jnp.float32
    assert Q_.dtype == jnp.float32
    st = online.init_state(Hb, T, C=2.0, V=2)
    assert st.omega.dtype == jnp.float32
    # f32 targets are not quantized down to bf16 before the Q matmul
    ref = Hb.astype(jnp.float32).T @ T
    np.testing.assert_allclose(Q_, ref, rtol=1e-5, atol=1e-5)


def test_merge_equals_concat():
    fmap, X, T = _problem(80, 5, 16, 2, seed=4)
    a = stats.from_raw(X[:30], T[:30], fmap)
    b = stats.from_raw(X[30:], T[30:], fmap)
    both = a.merge(b)
    ref = stats.from_raw(X, T, fmap)
    np.testing.assert_allclose(both.P, ref.P, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(both.Q, ref.Q, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(both.t_sq, ref.t_sq, rtol=1e-6)


def test_from_raw_chunk_option_and_nonfusable_fallback():
    fmap, X, T = _problem(100, 5, 24, 2, seed=5)
    ref = stats.from_raw(X, T, fmap)
    chunked = stats.from_raw(X, T, fmap, chunk=17)
    np.testing.assert_allclose(chunked.P, ref.P, rtol=1e-5, atol=1e-5)

    class OpaqueMap:  # not fusable: exercises the materialize path
        num_features = fmap.num_features

        def __call__(self, x):
            return fmap(x)

    opaque = stats.from_raw(X, T, OpaqueMap(), chunk=17)
    np.testing.assert_allclose(opaque.P, ref.P, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(opaque.Q, ref.Q, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("N,calls", [
    (96, [32]),          # three whole blocks, one batched call
    (101, [32, 5]),      # and the rows left over
    (63, [63]),          # under two blocks: one call, as before
])
def test_tall_node_moments_sum_row_blocks(N, calls):
    fmap, X, T = _problem(N, 4, 12, 2, seed=11)
    W, b, act = stats.fusable_params(fmap)
    seen = []

    def moments(x, t):
        seen.append(x.shape[0])
        return elm_stats_scan(x, W, b, t, activation=act, chunk=16)

    P, Q = elm_stats.by_row_blocks(moments, X, T, 32)
    assert seen == calls
    P0, Q0 = gram_ops.local_elm_stats(fmap(X), T)
    np.testing.assert_allclose(P, P0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(Q, Q0, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [1, 2, 5, 8])
def test_pairwise_sum_adds_every_term(n):
    x = jnp.arange(n * 6, dtype=jnp.float32).reshape(n, 2, 3)
    np.testing.assert_array_equal(elm_stats.pairwise_sum(x), x.sum(0))


@pytest.mark.interpret
def test_fused_kernel_sums_a_tall_node_in_row_blocks(arm):
    """Two whole blocks of ``BLOCK_ROWS`` rows and the rest: the sum of
    the three calls' moments, to the bit (the mirror is exact), and a
    symmetric P."""
    B = elm_stats.BLOCK_ROWS
    fmap, X, T = _problem(2 * B + 5, 3, 16, 2, seed=12)
    W, b, act = stats.fusable_params(fmap)
    kw = dict(activation=act, interpret=True, block_l=16, block_n=2048)
    P, Q = elm_stats_pallas(X, W, b, T, **kw)
    parts = [elm_stats_pallas(X[s:e], W, b, T[s:e], **kw)
             for s, e in ((0, B), (B, 2 * B), (2 * B, 2 * B + 5))]
    np.testing.assert_array_equal(P, (parts[0][0] + parts[1][0]) + parts[2][0])
    np.testing.assert_array_equal(Q, (parts[0][1] + parts[1][1]) + parts[2][1])
    np.testing.assert_array_equal(P, P.T)
    P0, Q0 = gram_ops.local_elm_stats(fmap(X), T)
    np.testing.assert_allclose(P, P0, rtol=1e-5)
    np.testing.assert_allclose(Q, Q0, rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Cholesky finalization — the only Omega producer
# ---------------------------------------------------------------------------


def test_finalize_matches_explicit_inverse():
    fmap, X, T = _problem(120, 6, 24, 3, seed=6)
    s = stats.from_raw(X, T, fmap)
    omega, beta0 = s.finalize(C=8.0, V=4)
    A = np.eye(24) / (4 * 8.0) + np.asarray(s.P, np.float64)
    ref = np.linalg.inv(A)
    # f32 factorization vs f64 inverse: differences are pure f32 noise
    np.testing.assert_allclose(omega, ref, rtol=5e-2, atol=2e-3)
    np.testing.assert_allclose(beta0, omega @ s.Q, rtol=1e-6, atol=1e-6)


def test_stats_plane_feeds_all_paths_identically():
    """dc_elm.init_node, online.init_state and elm.solve_from_stats all
    sit on the same Cholesky producer."""
    fmap, X, T = _problem(90, 4, 18, 2, seed=7)
    H = fmap(X)
    P_, Q_ = dc_elm.local_stats(H, T)
    omega_dc, beta_dc = dc_elm.init_node(P_, Q_, C=4.0, V=3)
    st = online.init_state(H, T, C=4.0, V=3)
    np.testing.assert_allclose(omega_dc, st.omega, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(beta_dc, st.beta, rtol=1e-6, atol=1e-6)
    beta_c = elm.solve_from_stats(P_, Q_, C=4.0)
    ref = np.linalg.solve(np.eye(18) / 4.0 + np.asarray(P_), np.asarray(Q_))
    np.testing.assert_allclose(beta_c, ref, rtol=1e-4, atol=1e-4)


def test_stream_init_raw_matches_hidden_path():
    V, Ni, D, L, M, C = 3, 40, 2, 12, 1, 2.0
    fmap = features.make_random_features(jax.random.key(0), D, L)
    ks = jax.random.split(jax.random.key(1), 2)
    X = jax.random.normal(ks[0], (V, Ni, D))
    T = jax.random.normal(ks[1], (V, Ni, M))
    from repro.core import consensus

    eng = engine.simulated_dc_elm(consensus.ring(V), C)
    via_h = eng.stream_init(jax.vmap(fmap)(X), T)
    via_raw = eng.stream_init(X_nodes=X, T_nodes=T, feature_map=fmap)
    np.testing.assert_allclose(via_raw.omegas, via_h.omegas, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(via_raw.Qs, via_h.Qs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(via_raw.betas, via_h.betas, rtol=1e-4,
                               atol=1e-5)
    with pytest.raises(ValueError, match="feature_map"):
        eng.stream_init(X_nodes=X, T_nodes=T)


def test_simulate_init_raw_matches_hidden_path():
    V, Ni, D, L = 4, 30, 3, 10
    fmap = features.make_random_features(jax.random.key(2), D, L)
    ks = jax.random.split(jax.random.key(3), 2)
    X = jax.random.normal(ks[0], (V, Ni, D))
    T = jax.random.normal(ks[1], (V, Ni, 2))
    s_raw, P_raw, Q_raw = dc_elm.simulate_init_raw(X, T, fmap, C=1.0)
    s_h, P_h, Q_h = dc_elm.simulate_init(jax.vmap(fmap)(X), T, C=1.0)
    np.testing.assert_allclose(P_raw, P_h, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(Q_raw, Q_h, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_raw.betas, s_h.betas, rtol=1e-4, atol=1e-5)


def test_f64_dtype_policy():
    """x64 fidelity inputs keep f64 moments (the stiff-C paper runs)."""
    fmap, X, T = _problem(50, 3, 8, 1, seed=8)
    with jax.enable_x64():
        X64 = jnp.asarray(np.asarray(X), jnp.float64)
        T64 = jnp.asarray(np.asarray(T), jnp.float64)
        fmap64 = features.RandomFeatureMap(
            weights=jnp.asarray(np.asarray(fmap.weights), jnp.float64),
            bias=jnp.asarray(np.asarray(fmap.bias), jnp.float64),
            activation=fmap.activation,
        )
        s = stats.from_raw(X64, T64, fmap64)
        assert s.P.dtype == jnp.float64
        omega, _ = s.finalize(C=256.0, V=2)
        assert omega.dtype == jnp.float64


# ---------------------------------------------------------------------------
# Feature-map satellites
# ---------------------------------------------------------------------------


def test_random_feature_map_validates_activation_at_construction():
    w, b = jnp.zeros((3, 4)), jnp.zeros((4,))
    with pytest.raises(ValueError) as ei:
        features.RandomFeatureMap(weights=w, bias=b, activation="bogus")
    msg = str(ei.value)
    for name in features.ACTIVATIONS:
        assert name in msg  # the error names every valid activation


def test_activation_registry_is_shared():
    assert set(features.valid_activations()) == set(
        features.ACTIVATIONS
    ) | {"rbf"}
    assert features._ACTIVATIONS is features.ACTIVATIONS


def test_rbf_expansion_matches_broadcast_reference():
    """||x||^2 - 2 x.c + ||c||^2 == the (..., L, D) broadcast, without
    ever building the (..., L, D) intermediate."""
    fmap = features.make_random_features(jax.random.key(4), 6, 25, "rbf")
    x = jax.random.normal(jax.random.key(5), (40, 6))
    got = fmap(x)
    d2 = jnp.sum(jnp.square(x[:, None, :] - fmap.centers), axis=-1)
    ref = jnp.exp(-fmap.gamma * d2)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    assert got.shape == (40, 25)


def test_rbf_batched_shapes():
    fmap = features.make_random_features(jax.random.key(6), 3, 9, "rbf")
    x = jax.random.normal(jax.random.key(7), (2, 5, 3))
    assert fmap(x).shape == (2, 5, 9)


# ---------------------------------------------------------------------------
# Hypothesis property: any split of N == one-shot (f32 tolerance)
# ---------------------------------------------------------------------------


def test_chunked_any_split_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(
        n=st.integers(20, 120),
        splits=st.lists(st.integers(1, 40), min_size=0, max_size=4),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=20, deadline=None)
    def prop(n, splits, seed):
        fmap, X, T = _problem(n, 3, 11, 2, seed=seed % 100)
        ref = stats.from_raw(X, T, fmap)
        cuts = sorted({min(s, n) for s in splits})
        bounds = [0] + cuts + [n]
        s = stats.SufficientStats.zero(11, 2)
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            if hi > lo:
                s = s.accumulate(X[lo:hi], T[lo:hi], fmap)
        np.testing.assert_allclose(s.P, ref.P, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s.Q, ref.Q, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s.t_sq, ref.t_sq, rtol=1e-5)

    prop()
