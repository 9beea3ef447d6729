"""Multi-tenant model plane: stacked-beta kernel parity, the versioned
TenantRegistry, mixed-tenant serving (one fused launch), differential
bitwise packing-independence, and publisher-thread concurrency."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_mod
from repro.core.features import make_random_features
from repro.kernels.elm_predict import elm_predict_stacked_pallas
from repro.kernels.elm_predict_ops import (
    fused_predict_stacked,
    predict_map,
    predict_stacked,
)
from repro.kernels.elm_predict_ref import (
    elm_predict_stacked_scan,
    predict_reference,
    predict_stacked_reference,
)
from repro.serving import (
    ContinuousELMServer,
    ELMServer,
    RetiredTenantError,
    TenantRegistry,
    UnknownTenantError,
)


def _relerr(a, b):
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (1 + jnp.max(jnp.abs(b))))


def _stacked_problem(N, D, L, M, T, dtype=jnp.float32,
                     activation="sigmoid", seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    X = jax.random.normal(ks[0], (N, D)).astype(dtype)
    W = jax.random.normal(ks[1], (D, L)).astype(dtype)
    if activation == "rbf":
        b = jax.random.uniform(ks[2], (L,), minval=0.05, maxval=1.0)
    else:
        b = jax.random.normal(ks[2], (L,))
    betas = jax.random.normal(ks[3], (T, L, M)).astype(jnp.float32)
    tids = jax.random.randint(ks[4], (N,), 0, T, jnp.int32)
    return X, W, b, betas, tids


def _loop_oracle(X, W, b, betas, tids, activation):
    """Per-tenant loop over the single-beta oracle: the semantics the
    stacked path must reproduce."""
    rows = [
        predict_reference(
            X[n:n + 1], W, b, betas[int(t)], activation=activation
        )
        for n, t in enumerate(np.asarray(tids))
    ]
    return jnp.concatenate(rows, axis=0)


# ---------------------------------------------------------------------------
# Stacked kernel parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "activation", ["sigmoid", "tanh", "relu", "sin", "identity", "rbf"]
)
def test_stacked_parity_activations(activation):
    """Reference == per-tenant loop; scan and Pallas match it."""
    X, W, b, betas, tids = _stacked_problem(
        70, 5, 66, 3, 7, activation=activation
    )
    ref = predict_stacked_reference(
        X, W, b, betas, tids, activation=activation
    )
    loop = _loop_oracle(X, W, b, betas, tids, activation)
    assert _relerr(ref, loop) < 2e-5
    scan = elm_predict_stacked_scan(
        X, W, b, betas, tids, activation=activation, chunk=32
    )
    assert _relerr(scan, ref) < 2e-5
    pal = elm_predict_stacked_pallas(
        X, W, b, betas, tids, activation=activation, interpret=True,
        block_l=32, block_n=32,
    )
    assert _relerr(pal, ref) < 2e-5


@pytest.mark.parametrize("N", [1, 5, 127, 256])
def test_stacked_parity_ragged_rows(N):
    """Row counts off the block grid: padded rows contribute nothing."""
    X, W, b, betas, tids = _stacked_problem(N, 4, 40, 2, 5, seed=N)
    ref = predict_stacked_reference(X, W, b, betas, tids)
    pal = elm_predict_stacked_pallas(
        X, W, b, betas, tids, interpret=True, block_l=16, block_n=64,
    )
    assert _relerr(pal, ref) < 2e-5
    scan = elm_predict_stacked_scan(X, W, b, betas, tids, chunk=33)
    assert _relerr(scan, ref) < 2e-5


def test_stacked_parity_bf16():
    X, W, b, betas, tids = _stacked_problem(
        64, 6, 48, 3, 4, dtype=jnp.bfloat16
    )
    ref = predict_stacked_reference(X, W, b, betas, tids)
    assert ref.dtype == jnp.float32  # f32 betas win the promotion
    pal = elm_predict_stacked_pallas(
        X, W, b, betas, tids, interpret=True, block_l=16, block_n=32,
    )
    assert _relerr(pal, ref) < 1e-2
    scan = elm_predict_stacked_scan(X, W, b, betas, tids, chunk=17)
    assert _relerr(scan, ref) < 1e-2


def test_stacked_single_tenant_matches_plain_predict():
    """T=1, all ids 0: the stacked path degenerates to plain predict."""
    X, W, b, betas, _ = _stacked_problem(50, 4, 30, 2, 1)
    tids = jnp.zeros((50,), jnp.int32)
    ref = predict_reference(X, W, b, betas[0])
    out = predict_stacked_reference(X, W, b, betas, tids)
    assert _relerr(out, ref) < 1e-6


def test_stacked_dispatcher_and_empty_batch():
    X, W, b, betas, tids = _stacked_problem(40, 4, 24, 2, 3)
    ref = predict_stacked_reference(X, W, b, betas, tids)
    for use_kernel in (False, True):
        out = fused_predict_stacked(
            X, W, b, betas, tids, use_kernel=use_kernel
        )
        assert _relerr(out, ref) < 2e-5
    fmap = make_random_features(jax.random.key(3), 4, 24)
    y0 = predict_stacked(X[:0], fmap, betas, tids[:0])
    assert y0.shape == (0, 2)


def test_predict_stacked_map_level_parity():
    """FeatureMap-level stacked predict == per-tenant predict_map."""
    fmap = make_random_features(jax.random.key(5), 6, 33)
    X, _, _, betas, tids = _stacked_problem(45, 6, 33, 3, 4, seed=5)
    out = predict_stacked(X, fmap, betas, tids)
    for n, t in enumerate(np.asarray(tids)):
        ref = predict_map(X[n:n + 1], fmap, betas[int(t)])
        assert _relerr(out[n:n + 1], ref) < 2e-5


def test_predict_stacked_feature_map_none():
    """feature_map=None: x already IS the feature matrix."""
    H = jax.random.normal(jax.random.key(0), (20, 16))
    betas = jax.random.normal(jax.random.key(1), (3, 16, 2))
    tids = jax.random.randint(jax.random.key(2), (20,), 0, 3, jnp.int32)
    out = predict_stacked(H, None, betas, tids)
    ref = jnp.stack([
        H[n] @ betas[int(t)] for n, t in enumerate(np.asarray(tids))
    ])
    assert _relerr(out, ref) < 1e-6


# ---------------------------------------------------------------------------
# Property test: stacked == per-tenant loop (hypothesis)
# ---------------------------------------------------------------------------


def _property_case(N, T, L, act, dtype_name, seed):
    dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    X, W, b, betas, tids = _stacked_problem(
        N, 3, L, 2, T, dtype=dtype, activation=act, seed=seed
    )
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    ref = _loop_oracle(X, W, b, betas, tids, act)
    scan = elm_predict_stacked_scan(
        X, W, b, betas, tids, activation=act, chunk=max(1, N // 2)
    )
    assert _relerr(scan, ref) < tol
    pal = elm_predict_stacked_pallas(
        X, W, b, betas, tids, activation=act, interpret=True,
        block_l=16, block_n=16,
    )
    assert _relerr(pal, ref) < tol


def test_property_stacked_equals_loop():
    """Hypothesis sweep: random tenant mixes, ragged row counts, every
    activation, f32 and bf16 — stacked predict == per-tenant loop
    within the pinned tolerance on BOTH fallbacks."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=20, deadline=None)
    @hyp.given(
        N=st.integers(1, 40),
        T=st.integers(1, 6),
        L=st.integers(1, 48),
        act=st.sampled_from(
            ["sigmoid", "tanh", "relu", "sin", "identity", "rbf"]
        ),
        dtype_name=st.sampled_from(["float32", "bfloat16"]),
        seed=st.integers(0, 2**16),
    )
    def prop(N, T, L, act, dtype_name, seed):
        _property_case(N, T, L, act, dtype_name, seed)

    prop()


# ---------------------------------------------------------------------------
# TenantRegistry
# ---------------------------------------------------------------------------


def _betas(L=16, M=2, seed=0, n=1):
    rng = np.random.default_rng(seed)
    out = [rng.normal(size=(L, M)).astype(np.float32) for _ in range(n)]
    return out[0] if n == 1 else out


def test_registry_versioning_and_snapshot():
    b1, b2 = _betas(n=2)
    reg = TenantRegistry()
    assert reg.publish("a", b1) == 1
    assert reg.publish("b", b1) == 1
    assert reg.publish("a", b2) == 2  # hot-swap bumps per-tenant
    assert reg.version == 3  # every publish bumps the global version
    snap = reg.snapshot()
    assert snap.num_tenants == 2
    assert snap.tenant_version("a") == 2
    np.testing.assert_array_equal(np.asarray(snap.beta("a")), b2)
    assert reg.snapshot() is snap  # cached until the next mutation
    reg.publish("b", b2)
    assert reg.snapshot() is not snap


def test_registry_init_mapping_and_retire_cycle():
    b1, b2 = _betas(n=2)
    reg = TenantRegistry({"a": b1, "b": b2})
    assert sorted(reg.tenant_ids) == ["a", "b"]
    reg.retire("a")
    assert sorted(reg.tenant_ids) == ["b"]
    with pytest.raises(RetiredTenantError):
        reg.tenant_version("a")
    with pytest.raises(RetiredTenantError):
        reg.retire("a")  # already retired: still the named error
    with pytest.raises(UnknownTenantError):
        reg.retire("never-seen")
    # re-registration resumes the version counter (no version reuse)
    assert reg.publish("a", b2) == 2
    snap = reg.snapshot()
    assert snap.tenant_version("a") == 2


def test_registry_named_errors_name_the_argument():
    reg = TenantRegistry({"a": _betas()})
    with pytest.raises(UnknownTenantError, match="registered tenants"):
        reg.tenant_version("zz")
    snap = reg.snapshot()
    reg.retire("a")
    reg.publish("b", _betas())
    snap = reg.snapshot()
    with pytest.raises(RetiredTenantError, match="re-register"):
        snap.slot("a")
    with pytest.raises(UnknownTenantError, match="registered tenants"):
        snap.slot("zz")
    with pytest.raises(ValueError, match="beta must be"):
        reg.publish("c", np.zeros((4,)))
    with pytest.raises(ValueError, match="registry serves"):
        reg.publish("c", np.zeros((3, 3), np.float32))
    with pytest.raises(ValueError, match="beta_mode must be one of"):
        TenantRegistry(beta_mode="fp64")
    with pytest.raises(ValueError, match="int8_tile must be"):
        TenantRegistry(int8_tile=0)


def test_registry_empty_snapshot_raises():
    with pytest.raises(RuntimeError, match="no live tenants"):
        TenantRegistry().snapshot()


def test_registry_stale_tenants_rule():
    b = _betas()
    reg = TenantRegistry({"a": b, "b": b})
    snap = reg.snapshot()
    assert reg.stale_tenants(snap, 0) == []
    reg.publish("a", b)
    assert reg.stale_tenants(snap, 0) == ["a"]
    assert reg.stale_tenants(snap, 1) == []  # within the bound
    reg.publish("c", b)  # live tenant the snapshot never saw
    assert "c" in reg.stale_tenants(snap, 99)


def test_registry_int8_publish_quantizes_and_accounts():
    L, M = 32, 4
    beta = _betas(L, M)
    reg = TenantRegistry(beta_mode="int8", int8_tile=16)
    reg.publish("a", beta)
    assert reg.metrics["beta_bytes"] > 0
    got = np.asarray(reg.snapshot().beta("a"))
    assert not np.array_equal(got, beta)  # actually quantized
    assert np.max(np.abs(got - beta)) < 0.2  # but close
    # deterministic in (uid, version): republishing the same beta after
    # a retire/re-register cycle lands on a later version -> new noise
    reg2 = TenantRegistry(beta_mode="int8", int8_tile=16)
    reg2.publish("a", beta)
    np.testing.assert_array_equal(
        np.asarray(reg2.snapshot().beta("a")), got
    )


def test_publisher_reduce_modes_and_stream_chunk_hook():
    L, M = 12, 2
    stacked = np.stack(_betas(L, M, n=3))
    reg = TenantRegistry()
    reg.publisher("u", reduce="mean").publish(stacked)
    np.testing.assert_allclose(
        np.asarray(reg.snapshot().beta("u")), stacked.mean(0), rtol=1e-6
    )
    reg.publisher("v", reduce=1).publish(stacked)
    np.testing.assert_allclose(
        np.asarray(reg.snapshot().beta("v")), stacked[1], rtol=1e-6
    )
    reg.publisher("w").publish(stacked[0])  # bare (L, M) passes through
    with pytest.raises(ValueError, match='reduce must be "mean"'):
        reg.publisher("x", reduce="median")
    with pytest.raises(ValueError, match="betas must be"):
        reg.publisher("x").publish(np.zeros((2, 2, 2, 2)))


def test_stream_chunk_publishes_into_registry():
    """ConsensusEngine.stream_chunk(publish_to=registry.publisher(t))
    lands the post-consensus model in that tenant's slot."""
    from repro.core import consensus

    V, D, Lh, Mh = 4, 4, 10, 2
    fmap = make_random_features(jax.random.key(0), D, Lh)
    ks = jax.random.split(jax.random.key(1), 2)
    H = jax.vmap(fmap)(jax.random.normal(ks[0], (V, 12, D)))
    T = jax.random.normal(ks[1], (V, 12, Mh))
    eng = engine_mod.simulated_dc_elm(consensus.paper_fig2(), 2.0**6)
    state = eng.stream_init(H, T)
    reg = TenantRegistry()
    state, _ = eng.stream_chunk(
        state, gamma=1 / 2.1, num_iters=100,
        publish_to=reg.publisher("user-7"),
    )
    assert reg.tenant_version("user-7") == 1
    np.testing.assert_allclose(
        np.asarray(reg.snapshot().beta("user-7")),
        np.asarray(state.betas.mean(0)),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# Multi-tenant serving
# ---------------------------------------------------------------------------


D, L, M = 5, 24, 2


def _mt_setup(T=4, seed=0, **kw):
    fmap = make_random_features(jax.random.key(7), D, L)
    rng = np.random.default_rng(seed)
    reg = TenantRegistry({
        f"t{i}": rng.normal(size=(L, M)).astype(np.float32)
        for i in range(T)
    })
    return fmap, reg, ELMServer(fmap, reg, **kw), rng


def test_server_mixed_tenants_one_launch():
    """A micro-batch mixing many tenants is served by ONE launch."""
    fmap, reg, srv, rng = _mt_setup(T=6, buckets=(64,))
    xs = {
        i: rng.normal(size=(4, D)).astype(np.float32) for i in range(6)
    }
    uids = {
        srv.submit(xs[i], tenant=f"t{i}"): i for i in list(range(6)) * 2
    }
    out = srv.flush()
    assert srv.metrics["batches"] == 1
    assert len(out) == 12
    snap = srv._snap
    for r in out:
        i = uids[r.uid]
        assert r.tenant == f"t{i}"
        assert r.version == snap.tenant_version(r.tenant)
        ref = predict_map(jnp.asarray(xs[i]), fmap, snap.beta(f"t{i}"))
        assert _relerr(r.y, ref) < 2e-5


def test_server_mode_mismatch_errors_name_the_argument():
    fmap, reg, srv, rng = _mt_setup()
    x = rng.normal(size=(2, D)).astype(np.float32)
    with pytest.raises(ValueError, match="tenant= is required"):
        srv.submit(x)
    with pytest.raises(ValueError, match="node= applies to single-tenant"):
        srv.submit(x, node=0, tenant="t0")
    with pytest.raises(UnknownTenantError, match="registered tenants"):
        srv.submit(x, tenant="zz")
    reg.retire("t0")
    with pytest.raises(RetiredTenantError, match="re-register"):
        srv.submit(x, tenant="t0")
    single = ELMServer(fmap, _betas(L, M))
    with pytest.raises(ValueError, match="tenant= applies to multi-tenant"):
        single.submit(x, tenant="t0")


def test_server_validation_errors_name_argument_and_values():
    fmap, reg, _, rng = _mt_setup()
    with pytest.raises(ValueError, match="max_staleness must be >= 0"):
        ELMServer(fmap, reg, max_staleness=-1)
    with pytest.raises(ValueError, match="int8_tile must be a positive"):
        ELMServer(fmap, reg, int8_tile=-8)
    with pytest.raises(ValueError, match="buckets must be ascending"):
        ELMServer(fmap, reg, buckets=(64, 16))
    with pytest.raises(ValueError, match="beta_mode must be one of"):
        ELMServer(fmap, reg, beta_mode="int4")
    with pytest.raises(ValueError, match="slots must be a positive"):
        ContinuousELMServer(fmap, reg, slots=0)
    with pytest.raises(ValueError, match="deadline_slack_s must be >= 0"):
        ContinuousELMServer(fmap, reg, deadline_slack_s=-0.5)
    with pytest.raises(ValueError, match="min_fill must be in"):
        ContinuousELMServer(fmap, reg, min_fill=1.5)
    srv = ELMServer(fmap, reg)
    with pytest.raises(ValueError, match="rows"):
        srv.submit(np.zeros((0, D), np.float32), tenant="t0")
    srv.submit(rng.normal(size=(1, D)).astype(np.float32), tenant="t0")
    with pytest.raises(ValueError, match="width"):
        srv.submit(np.zeros((1, D + 3), np.float32), tenant="t0")


def test_server_per_tenant_staleness_and_version_pinning():
    """A publish to tenant A refreshes requests *for A*; the flush
    snapshot pins every request's per-tenant version."""
    fmap, reg, srv, rng = _mt_setup(max_staleness=0)
    x = rng.normal(size=(2, D)).astype(np.float32)
    srv.predict(x, tenant="t0")  # prime the snapshot
    reg.publish("t1", rng.normal(size=(L, M)).astype(np.float32))
    srv.submit(x, tenant="t0")
    srv.submit(x, tenant="t1")
    out = srv.flush()
    by_tenant = {r.tenant: r for r in out}
    assert by_tenant["t1"].version == 2  # saw the fresh publish
    assert by_tenant["t0"].version == 1
    # a frozen server keeps serving the pinned snapshot
    srv.freeze()
    reg.publish("t0", rng.normal(size=(L, M)).astype(np.float32))
    srv.submit(x, tenant="t0")
    assert srv.flush()[0].version == 1
    srv.thaw()
    srv.submit(x, tenant="t0")
    assert srv.flush()[0].version == 2


def test_server_oversized_split_pins_one_version():
    fmap, reg, srv, rng = _mt_setup(buckets=(8,))
    x = rng.normal(size=(29, D)).astype(np.float32)  # 4 chunks
    uid = srv.submit(x, tenant="t2")
    out = srv.flush()
    (r,) = [r for r in out if r.uid == uid]
    assert r.y.shape == (29, M)
    assert r.version == srv._snap.tenant_version("t2")
    ref = predict_map(jnp.asarray(x), fmap, srv._snap.beta("t2"))
    assert _relerr(r.y, ref) < 2e-5


def test_server_int8_stacked_arm():
    fmap, reg, srv, rng = _mt_setup(beta_mode="int8", int8_tile=16)
    x = rng.normal(size=(3, D)).astype(np.float32)
    y = srv.predict(x, tenant="t1")
    assert srv.metrics["beta_bytes"] > 0
    ref = predict_map(jnp.asarray(x), fmap, srv._snap.beta("t1"))
    assert _relerr(y, ref) < 0.3  # quantized but close
    assert not np.allclose(y, np.asarray(ref))  # actually quantized


def test_server_rejects_tenant_retired_mid_queue():
    """A tenant retired between submit and flush rejects with the
    named error in server.rejections; the flush still serves others."""
    fmap, reg, srv, rng = _mt_setup(max_staleness=0)
    x = rng.normal(size=(2, D)).astype(np.float32)
    srv.predict(x, tenant="t0")  # prime
    uid_dead = srv.submit(x, tenant="t3")
    uid_live = srv.submit(x, tenant="t1")
    reg.retire("t3")
    reg.publish("t1", rng.normal(size=(L, M)))  # forces the refresh
    out = srv.flush()
    assert [r.uid for r in out] == [uid_live]
    ((uid, tenant, err),) = srv.rejections
    assert uid == uid_dead and tenant == "t3"
    assert isinstance(err, RetiredTenantError)
    assert srv.metrics["rejected"] == 1


# ---------------------------------------------------------------------------
# Differential serving: packing independence, bitwise
# ---------------------------------------------------------------------------


def _serve_requests(reqs, *, buckets, seed=0, flush_each=False):
    """Serve (tenant, x) requests on a fresh server; returns uid -> y."""
    fmap = make_random_features(jax.random.key(7), D, L)
    rng = np.random.default_rng(seed)
    reg = TenantRegistry({
        f"t{i}": rng.normal(size=(L, M)).astype(np.float32)
        for i in range(4)
    })
    srv = ELMServer(fmap, reg, buckets=buckets)
    out = {}
    for tenant, x in reqs:
        uid = srv.submit(x, tenant=tenant)
        if flush_each:
            for r in srv.flush():
                out[r.uid] = r.y
    for r in srv.flush():
        out[r.uid] = r.y
    return out, srv


def test_differential_mixed_vs_single_tenant_bitwise():
    """One mixed-tenant bucket == the same requests served in
    single-tenant buckets, BITWISE (per-row results are independent of
    launch packing)."""
    rng = np.random.default_rng(3)
    reqs = [
        (f"t{i % 4}", rng.normal(size=(3, D)).astype(np.float32))
        for i in range(8)
    ]
    mixed, srv_m = _serve_requests(reqs, buckets=(32,))
    single, srv_s = _serve_requests(reqs, buckets=(32,), flush_each=True)
    assert srv_m.metrics["batches"] == 1
    assert srv_s.metrics["batches"] == 8  # one launch per request
    assert mixed.keys() == single.keys()
    for uid in mixed:
        np.testing.assert_array_equal(mixed[uid], single[uid])


def test_differential_oversized_split_bitwise():
    """An oversized request split across stacked launches reassembles
    bitwise-identically to dedicated single-tenant service."""
    rng = np.random.default_rng(4)
    big = ("t1", rng.normal(size=(21, D)).astype(np.float32))
    small = [
        (f"t{i % 4}", rng.normal(size=(2, D)).astype(np.float32))
        for i in range(3)
    ]
    mixed, _ = _serve_requests([big] + small, buckets=(8,))
    alone, _ = _serve_requests([big] + small, buckets=(8,),
                               flush_each=True)
    for uid in mixed:
        np.testing.assert_array_equal(mixed[uid], alone[uid])


# ---------------------------------------------------------------------------
# Concurrency: publisher threads vs a flushing server
# ---------------------------------------------------------------------------


def test_concurrent_publish_swap_retire_no_version_straddle():
    """Publisher threads register/hot-swap/retire while the server
    flushes. Distinguishable betas (version-scaled) prove no response
    ever mixes two versions; retired tenants reject with the named
    error and everything else keeps serving."""
    fmap = make_random_features(jax.random.key(7), D, L)
    rng = np.random.default_rng(9)
    base = {
        f"t{i}": rng.normal(size=(L, M)).astype(np.float32)
        for i in range(4)
    }
    reg = TenantRegistry(base)
    srv = ELMServer(fmap, reg, buckets=(64,), max_staleness=0)
    stop = threading.Event()
    errors = []

    def publisher(tenant):
        v = 1
        while not stop.is_set():
            try:
                v = reg.publish(tenant, base[tenant] * (v + 1))
                if v % 7 == 0:
                    reg.retire(tenant)
                    v = reg.publish(tenant, base[tenant] * (v + 2))
            except Exception as e:  # pragma: no cover - fail loudly
                errors.append(e)
                return

    threads = [
        threading.Thread(target=publisher, args=(f"t{i}",))
        for i in range(3)  # t3 stays at its seed version
    ]
    for t in threads:
        t.start()
    x = rng.normal(size=(5, D)).astype(np.float32)
    Hx = np.asarray(fmap(jnp.asarray(x)))
    served = 0
    transient_rejects = 0
    try:
        for _ in range(60):
            for i in range(4):
                try:
                    srv.submit(x, tenant=f"t{i}")
                except RetiredTenantError:
                    # submitted inside a publisher's retire->republish
                    # window: the named rejection is the contract
                    transient_rejects += 1
            for r in srv.flush():
                served += 1
                # the served beta must be base * k for ONE integer k:
                # a straddled response would mix two scalings
                expect_unit = Hx @ base[r.tenant]
                scale = r.y / np.where(
                    np.abs(expect_unit) < 1e-9, 1.0, expect_unit
                )
                ks = scale[np.abs(expect_unit) > 1e-3]
                assert ks.size
                k = np.round(ks.flat[0])
                np.testing.assert_allclose(ks, k, rtol=1e-4, atol=1e-4)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors
    assert served > 0
    # mid-queue rejections (tenant retired after submit) all carry the
    # named error
    assert all(
        isinstance(e, (RetiredTenantError, UnknownTenantError))
        for _, _, e in srv.rejections
    )
    reg.retire("t3")
    with pytest.raises(RetiredTenantError):
        srv.submit(x, tenant="t3")


# ---------------------------------------------------------------------------
# Continuous batching, multi-tenant
# ---------------------------------------------------------------------------


def test_continuous_mixed_tenants_and_refill():
    fmap = make_random_features(jax.random.key(7), D, L)
    rng = np.random.default_rng(11)
    reg = TenantRegistry({
        f"t{i}": rng.normal(size=(L, M)).astype(np.float32)
        for i in range(3)
    })
    srv = ContinuousELMServer(fmap, reg, slots=8)
    xs = {i: rng.normal(size=(6, D)).astype(np.float32) for i in range(3)}
    uids = {srv.submit(xs[i], tenant=f"t{i}"): i for i in range(3)}
    done = srv.flush()  # 18 rows through 8 slots: mid-flight refill
    assert len(done) == 3
    snap = srv._snap
    for r in done:
        i = uids[r.uid]
        ref = predict_map(jnp.asarray(xs[i]), fmap, snap.beta(f"t{i}"))
        assert _relerr(r.y, ref) < 2e-5
        assert r.version == snap.tenant_version(r.tenant)


def test_continuous_pins_first_launch_version_mid_flight():
    """Rows spanning steps are all served by the version pinned at the
    request's first launch, even when the tenant republishes between
    steps."""
    fmap = make_random_features(jax.random.key(7), D, L)
    rng = np.random.default_rng(12)
    beta1 = rng.normal(size=(L, M)).astype(np.float32)
    reg = TenantRegistry({"a": beta1})
    srv = ContinuousELMServer(fmap, reg, slots=4, max_staleness=0)
    x = rng.normal(size=(10, D)).astype(np.float32)
    uid = srv.submit(x, tenant="a")
    assert srv.step() == []  # 4 of 10 rows served, mid-flight
    reg.publish("a", beta1 * 10.0)  # lands mid-request
    out = []
    while not out:
        out = srv.step()
    (r,) = out
    assert r.uid == uid and r.version == 1
    ref = predict_map(jnp.asarray(x), fmap, jnp.asarray(beta1))
    assert _relerr(r.y, ref) < 2e-5  # ALL rows from the pinned beta
    # the next request picks up the published version
    y2 = srv.predict(x[:2], tenant="a")
    assert _relerr(y2, 10.0 * np.asarray(ref[:2])) < 2e-5


def test_continuous_rejects_retired_at_refresh():
    fmap = make_random_features(jax.random.key(7), D, L)
    rng = np.random.default_rng(13)
    reg = TenantRegistry({
        "a": rng.normal(size=(L, M)).astype(np.float32),
        "b": rng.normal(size=(L, M)).astype(np.float32),
    })
    srv = ContinuousELMServer(fmap, reg, slots=8, max_staleness=0)
    srv.predict(rng.normal(size=(1, D)).astype(np.float32), tenant="a")
    uid_dead = srv.submit(
        rng.normal(size=(2, D)).astype(np.float32), tenant="b"
    )
    reg.retire("b")
    reg.publish("a", rng.normal(size=(L, M)))  # forces the refresh
    srv.submit(rng.normal(size=(2, D)).astype(np.float32), tenant="a")
    out = srv.flush()
    assert [r.tenant for r in out] == ["a"]
    ((uid, tenant, err),) = srv.rejections
    assert uid == uid_dead and tenant == "b"
    assert isinstance(err, RetiredTenantError)


def test_stacked_dispatcher_consults_tuning_dict():
    X, W, b, betas, tids = _stacked_problem(32, 4, 16, 2, 3)
    ref = predict_stacked_reference(X, W, b, betas, tids)
    out = fused_predict_stacked(
        X, W, b, betas, tids, use_kernel=False, chunk=8
    )
    assert _relerr(out, ref) < 2e-5
    with pytest.raises(ValueError, match="chunk is the scan-fallback"):
        fused_predict_stacked(
            X, W, b, betas, tids, use_kernel=True, chunk=8
        )
