"""Hypothesis property-based tests on the system's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import consensus, dc_elm, elm, gossip, online
from repro.models.layers import chunked_cross_entropy, cross_entropy

_SMALL = dict(max_examples=20, deadline=None)


@given(
    n=st.integers(5, 40),
    l=st.integers(2, 12),
    m=st.integers(1, 3),
    c=st.floats(0.1, 100.0),
    seed=st.integers(0, 2**30),
)
@settings(**_SMALL)
def test_ridge_primal_dual_equivalence(n, l, m, c, seed):
    k1, k2 = jax.random.split(jax.random.key(seed))
    H = jax.random.normal(k1, (n, l), jnp.float32)
    T = jax.random.normal(k2, (n, m), jnp.float32)
    b1 = elm.ridge_primal(H, T, c)
    b2 = elm.ridge_dual(H, T, c)
    np.testing.assert_allclose(b1, b2, rtol=2e-2, atol=2e-3)


@given(
    v=st.integers(2, 10),
    gamma=st.floats(0.01, 0.45),
    seed=st.integers(0, 2**30),
)
@settings(**_SMALL)
def test_mixing_preserves_network_average(v, gamma, seed):
    """The consensus step conserves sum_i beta_i on any symmetric graph."""
    g = consensus.ring(v)
    adj = jnp.asarray(g.adjacency, jnp.float32)
    betas = jax.random.normal(jax.random.key(seed), (v, 3, 2))
    # identity-metric mixing (Omega = I): paper rule conserves the mean
    omegas = jnp.broadcast_to(jnp.eye(3), (v, 3, 3))
    state = dc_elm.DCELMState(betas=betas, omegas=omegas,
                              k=jnp.zeros((), jnp.int32))
    out = dc_elm.simulate_step(state, adj, jnp.asarray(gamma), C=1.0 / v)
    np.testing.assert_allclose(
        jnp.sum(out.betas, 0), jnp.sum(betas, 0), rtol=1e-4, atol=1e-4
    )


@given(
    n=st.integers(10, 60),
    dn=st.integers(1, 8),
    l=st.integers(2, 10),
    m=st.integers(1, 3),
    c=st.floats(0.2, 50.0),
    v=st.integers(1, 12),
    dtype=st.sampled_from(["float32", "float64"]),
    seed=st.integers(0, 2**30),
)
@settings(**_SMALL)
def test_woodbury_add_then_remove_roundtrips_state(
    n, dn, l, m, c, v, dtype, seed
):
    """add(S, d) then remove(..., d) round-trips the FULL state (omega
    AND Q) to the original, across random shapes/dtypes/constants."""
    with _dtype_ctx(dtype):
        dt = jnp.dtype(dtype)
        ks = jax.random.split(jax.random.key(seed), 4)
        H = jax.random.normal(ks[0], (n, l), dt) / np.sqrt(l)
        T = jax.random.normal(ks[1], (n, m), dt)
        dH = jax.random.normal(ks[2], (dn, l), dt) / np.sqrt(l)
        dT = jax.random.normal(ks[3], (dn, m), dt)
        s0 = online.init_state(H, T, C=c, V=v)
        s1 = online.remove_chunk(online.add_chunk(s0, dH, dT), dH, dT)
        tol = dict(rtol=1e-2, atol=1e-3) if dtype == "float32" else dict(
            rtol=1e-8, atol=1e-10
        )
        np.testing.assert_allclose(s1.omega, s0.omega, **tol)
        np.testing.assert_allclose(s1.Q, s0.Q, **tol)
        # and the reverse ordering: remove a real chunk, then re-add it
        s2 = online.add_chunk(
            online.remove_chunk(s0, H[:dn], T[:dn]), H[:dn], T[:dn]
        )
        np.testing.assert_allclose(s2.omega, s0.omega, **tol)
        np.testing.assert_allclose(s2.Q, s0.Q, **tol)


@given(
    n=st.integers(10, 60),
    dn=st.integers(1, 8),
    l=st.integers(2, 10),
    m=st.integers(1, 3),
    c=st.floats(0.2, 50.0),
    v=st.integers(1, 12),
    dtype=st.sampled_from(["float32", "float64"]),
    seed=st.integers(0, 2**30),
)
@settings(**_SMALL)
def test_woodbury_matches_direct_state(n, dn, l, m, c, v, dtype, seed):
    """Woodbury add/remove == online.direct_state (the O(L^3)
    recompute-from-scratch reference) on the surviving data."""
    with _dtype_ctx(dtype):
        dt = jnp.dtype(dtype)
        ks = jax.random.split(jax.random.key(seed), 4)
        H = jax.random.normal(ks[0], (n, l), dt) / np.sqrt(l)
        T = jax.random.normal(ks[1], (n, m), dt)
        dH = jax.random.normal(ks[2], (dn, l), dt) / np.sqrt(l)
        dT = jax.random.normal(ks[3], (dn, m), dt)
        s = online.init_state(H, T, C=c, V=v)
        s = online.add_chunk(s, dH, dT)
        s = online.remove_chunk(s, H[:dn], T[:dn])
        H2 = jnp.concatenate([H[dn:], dH])
        T2 = jnp.concatenate([T[dn:], dT])
        ref = online.direct_state(H2, T2, C=c, V=v)
        tol = dict(rtol=2e-2, atol=2e-3) if dtype == "float32" else dict(
            rtol=1e-7, atol=1e-9
        )
        np.testing.assert_allclose(s.omega, ref.omega, **tol)
        np.testing.assert_allclose(s.Q, ref.Q, **tol)
        np.testing.assert_allclose(s.beta, ref.beta, **tol)


def _dtype_ctx(dtype: str):
    """x64 mode for float64 draws; a no-op context for float32."""
    import contextlib

    if dtype == "float64":
        return jax.enable_x64()
    return contextlib.nullcontext()


@given(
    v=st.sampled_from([2, 4, 8, 16]),
    kind=st.sampled_from(["ring", "hypercube", "complete"]),
)
@settings(**_SMALL)
def test_gossip_spec_consistent_with_graph(v, kind):
    spec = gossip.GossipSpec(axes=("data",), kinds=(kind,))
    sizes = {"data": v}
    g = spec.to_graph(sizes)
    assert g.num_nodes == spec.num_nodes(sizes)
    assert g.d_max == spec.degree(sizes)
    assert g.is_connected
    assert spec.gamma_upper_bound(sizes) == 1.0 / g.d_max


@given(
    b=st.integers(1, 3),
    s=st.integers(2, 33),
    v=st.integers(5, 40),
    chunk=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2**30),
)
@settings(**_SMALL)
def test_chunked_ce_equals_full(b, s, v, chunk, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    d = 8
    h = jax.random.normal(ks[0], (b, s, d))
    table = jax.random.normal(ks[1], (v, d))
    labels = jax.random.randint(ks[2], (b, s), -1, v)
    full = cross_entropy(jnp.einsum("bsd,vd->bsv", h, table), labels)
    chunked = chunked_cross_entropy(h, table, labels, chunk=chunk)
    np.testing.assert_allclose(full, chunked, rtol=1e-4, atol=1e-5)


@given(
    n=st.integers(2, 50),
    l=st.integers(1, 8),
    seed=st.integers(0, 2**30),
)
@settings(**_SMALL)
def test_gram_kernel_property(n, l, seed):
    from repro.kernels.gram import gram_pallas

    H = jax.random.normal(jax.random.key(seed), (n, l))
    P = gram_pallas(H, interpret=True, block_l=8, block_n=16)
    np.testing.assert_allclose(P, H.T @ H, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(P, P.T, atol=1e-4)


@given(
    s=st.integers(3, 40),
    q=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2**30),
)
@settings(**_SMALL)
def test_ssd_chunk_invariance(s, q, seed):
    """SSD output must not depend on the chunk size."""
    from repro.kernels.ssd_ref import ssd_naive_reference, ssd_reference

    b, nh, hd, ds = 1, 2, 4, 4
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (b, s, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, s, ds))
    C = jax.random.normal(ks[4], (b, s, ds))
    y1, h1 = ssd_reference(x, dt, A, B, C, chunk=q)
    y2, h2 = ssd_naive_reference(x, dt, A, B, C)
    np.testing.assert_allclose(y1, y2, rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(h1, h2, rtol=5e-3, atol=5e-3)


@given(
    v=st.integers(2, 8),
    iters=st.integers(1, 30),
    seed=st.integers(0, 2**30),
)
@settings(max_examples=10, deadline=None)
def test_dc_elm_monotone_lyapunov(v, iters, seed):
    """Thm 1's Lyapunov argument: disagreement never increases."""
    ks = jax.random.split(jax.random.key(seed), 2)
    H = jax.random.normal(ks[0], (v, 20, 6))
    T = jax.random.normal(ks[1], (v, 20, 1))
    g = consensus.complete(v)
    state, _, _ = dc_elm.simulate_init(H, T, C=8.0)
    adj = jnp.asarray(g.adjacency, jnp.float32)
    gamma = jnp.asarray(g.default_gamma())
    prev = float(dc_elm.consensus_error(state.betas))
    for _ in range(iters):
        state = dc_elm.simulate_step(state, adj, gamma, C=8.0)
        cur = float(dc_elm.consensus_error(state.betas))
        assert cur <= prev * 1.01 + 1e-7
        prev = cur
