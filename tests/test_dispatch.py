"""The kernel-dispatch policy (kernels/dispatch.py): which arm a
dispatcher takes, how block kwargs map onto it, and the block sizes a
caller who passes none gets."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (
    dispatch,
    elm_gossip_ops,
    elm_predict_ops,
    elm_stats_ops,
)
from repro.kernels import elm_gossip_ref as gossip_ref
from repro.kernels.dispatch import scan_kwargs
from repro.kernels.elm_predict_ref import (
    elm_predict_scan,
    elm_predict_stacked_scan,
)
from repro.kernels.elm_stats_ref import elm_stats_scan, preact_stats_scan


def _stats_problem(N=256, D=5, L=33, M=3, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (
        jax.random.normal(ks[0], (N, D)),
        jax.random.normal(ks[1], (D, L)),
        jax.random.normal(ks[2], (L,)),
        jax.random.normal(ks[3], (N, M)),
    )


# ---------------------------------------------------------------------------
# The policy table: backend x REPRO_FORCE_INTERPRET x use_kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel", [None, True, False])
@pytest.mark.parametrize("env", [None, "0", "1"])
@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_dispatch_policy_table(monkeypatch, backend, env, use_kernel):
    monkeypatch.setattr(dispatch, "on_tpu", lambda: backend == "tpu")
    if env is None:
        monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", env)
    forced = env == "1"
    assert dispatch.force_interpret() == forced
    if use_kernel is not None:
        want = use_kernel  # an explicit caller choice always wins
    else:
        want = backend == "tpu" or forced
    assert dispatch.wants_kernel(use_kernel) == want


# ---------------------------------------------------------------------------
# Block kwargs: scan_kwargs / pallas_kwargs
# ---------------------------------------------------------------------------


def test_scan_kwargs_block_n_maps_to_chunk():
    assert scan_kwargs({"block_n": 128}) == {"chunk": 128}
    assert scan_kwargs({"chunk": 64}) == {"chunk": 64}
    assert scan_kwargs({"block_l": None, "block_n": None}) == {}


def test_scan_kwargs_block_l_raises():
    with pytest.raises(ValueError, match="block_l"):
        scan_kwargs({"block_l": 64})
    with pytest.raises(ValueError, match="block_l"):
        elm_stats_ops.fused_moments(
            *_stats_problem(), use_kernel=False, block_l=64
        )


def test_scan_kwargs_conflict_raises():
    with pytest.raises(ValueError, match="both block_n"):
        scan_kwargs({"block_n": 128, "chunk": 64})


def test_block_n_honored_bitwise_by_scan_path():
    """block_n=k through the dispatcher == chunk=k directly."""
    X, W, b, T = _stats_problem()
    P1, Q1 = elm_stats_ops.fused_moments(
        X, W, b, T, use_kernel=False, block_n=96
    )
    P2, Q2 = elm_stats_scan(X, W, b, T, chunk=96)
    assert np.array_equal(np.asarray(P1), np.asarray(P2))
    assert np.array_equal(np.asarray(Q1), np.asarray(Q2))


def _rejects_chunk_calls():
    X, W, b, T = _stats_problem(N=64, D=4, L=32, M=2)
    beta = jnp.ones((32, 2))
    betas = jnp.ones((3, 32, 2))
    tids = jnp.zeros((64,), jnp.int32)
    return {
        "fused_moments": (elm_stats_ops.fused_moments, (X, W, b, T)),
        "fused_preact_moments": (
            elm_stats_ops.fused_preact_moments, (X @ W, b, T)
        ),
        "fused_predict": (elm_predict_ops.fused_predict, (X, W, b, beta)),
        "fused_predict_stacked": (
            elm_predict_ops.fused_predict_stacked, (X, W, b, betas, tids)
        ),
    }


@pytest.mark.parametrize(
    "dispatcher",
    [
        "fused_moments",
        "fused_preact_moments",
        "fused_predict",
        "fused_predict_stacked",
    ],
)
def test_pallas_path_rejects_chunk(dispatcher):
    fn, args = _rejects_chunk_calls()[dispatcher]
    with pytest.raises(ValueError, match="chunk is the scan-fallback knob"):
        fn(*args, use_kernel=True, chunk=32)


def test_fused_predict_explicit_chunk():
    X, W, b, T = _stats_problem()
    beta = jax.random.normal(jax.random.key(9), (W.shape[1], 3))
    y0 = elm_predict_ops.fused_predict(X, W, b, beta, use_kernel=False)
    y1 = elm_predict_ops.fused_predict(
        X, W, b, beta, use_kernel=False, chunk=64
    )
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-5)


# ---------------------------------------------------------------------------
# No block kwargs: each scan fallback runs at its named default, bitwise
# ---------------------------------------------------------------------------


def _stats_default():
    X, W, b, T = _stats_problem(N=2048 + 96, D=4, L=16, M=2)
    got = elm_stats_ops.fused_moments(X, W, b, T, use_kernel=False)
    return got, elm_stats_scan(X, W, b, T, chunk=2048)


def _preact_default():
    X, W, b, T = _stats_problem(N=2048 + 96, D=4, L=16, M=2)
    Z = X @ W
    got = elm_stats_ops.fused_preact_moments(Z, b, T, use_kernel=False)
    return got, preact_stats_scan(Z, b, T, chunk=2048)


def _predict_default():
    X, W, b, _ = _stats_problem(N=4096 + 96, D=4, L=16, M=2)
    beta = jax.random.normal(jax.random.key(9), (16, 2))
    got = elm_predict_ops.fused_predict(X, W, b, beta, use_kernel=False)
    return got, elm_predict_scan(X, W, b, beta, chunk=4096)


def _stacked_default():
    X, W, b, _ = _stats_problem(N=2048 + 96, D=4, L=16, M=2)
    betas = jax.random.normal(jax.random.key(9), (3, 16, 2))
    tids = jax.random.randint(
        jax.random.key(10), (X.shape[0],), 0, 3, jnp.int32
    )
    got = elm_predict_ops.fused_predict_stacked(
        X, W, b, betas, tids, use_kernel=False
    )
    return got, elm_predict_stacked_scan(X, W, b, betas, tids, chunk=2048)


def _gossip_problem():
    """A complete graph on 12 nodes: d_max 11, over the 8-slot chunk."""
    V, L, M = 12, 8, 3
    adj = jnp.ones((V, V), jnp.float32) - jnp.eye(V, dtype=jnp.float32)
    idx, w, deg = gossip_ref.neighbor_lists(adj)
    ks = jax.random.split(jax.random.key(4), 2)
    betas = jax.random.normal(ks[0], (V, L, M), jnp.float32)
    a = jax.random.normal(ks[1], (V, L, L), jnp.float32)
    omegas = jnp.einsum("vlk,vmk->vlm", a, a) / L
    return betas, omegas, idx, w, deg


def _gossip_rounds_default():
    betas, omegas, idx, w, deg = _gossip_problem()
    got = elm_gossip_ops.fused_gossip_rounds(
        betas, omegas, idx, w, deg, 0.01, num_rounds=3, use_kernel=False
    )
    scan = jax.jit(
        gossip_ref.elm_gossip_scan,
        static_argnames=("num_rounds", "compress", "chunk"),
    )
    want = scan(betas, omegas, idx, w, deg, 0.01, num_rounds=3, chunk=8)
    return got, want


def _gossip_round_default():
    betas, omegas, idx, w, deg = _gossip_problem()
    payload = betas.astype(jnp.bfloat16).astype(jnp.float32)
    got = elm_gossip_ops.fused_gossip_round(
        betas, payload, omegas, idx[0], w[0], deg[0], 0.01, use_kernel=False
    )
    round_fn = jax.jit(
        gossip_ref.gossip_round_payload, static_argnames=("chunk",)
    )
    want = round_fn(
        betas, payload, omegas, idx[0], w[0], deg[0], 0.01, chunk=8
    )
    return got, want


@pytest.mark.parametrize(
    "case",
    [
        _stats_default,
        _preact_default,
        _predict_default,
        _stacked_default,
        _gossip_rounds_default,
        _gossip_round_default,
    ],
    ids=lambda f: f.__name__.strip("_"),
)
def test_no_block_kwargs_equals_named_defaults(case):
    got, want = case()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert np.array_equal(np.asarray(g), np.asarray(w))
