"""Fused gossip-round kernel plane (kernels/elm_gossip*).

Pins, per DESIGN.md §15: neighbor-list construction, scan-fallback and
Pallas-interpret parity against the dense DenseMixer round (plain,
chunked, bf16, explicit-payload, time-varying, fault-masked), the
in-kernel multi-round arm, engine-level NeighborMixer composition
(FaultyMixer / CompressedMixer / membership churn), int8 bitwise
determinism, and the dense-fallback heuristic.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine as engine_lib
from repro.core.compression import CompressionSpec
from repro.core.consensus import (
    FaultModel,
    alternating_halves,
    build,
    random_geometric,
)
from repro.core.mixers import DenseMixer, NeighborMixer
from repro.kernels import elm_gossip_ops
from repro.kernels import elm_gossip_ref as ref
from repro.kernels.elm_gossip import (
    elm_gossip_pallas,
    elm_gossip_pallas_multiround,
    fit_block_v,
    multiround_vmem_bytes,
    round_vmem_bytes,
)
from repro.kernels.vmem import DEFAULT_SCOPED_VMEM, vmem_budget

TOL = dict(rtol=2e-5, atol=2e-5)


def _adj(g):
    return jnp.asarray(np.asarray(g.adjacency), jnp.float32)


def _state(V, L, M, seed=0):
    ks = jax.random.split(jax.random.key(seed), 2)
    betas = jax.random.normal(ks[0], (V, L, M), jnp.float32)
    w = jax.random.normal(ks[1], (V, L, L), jnp.float32)
    omegas = jnp.einsum("vlk,vmk->vlm", w, w) / L
    return betas, omegas


def _dense_rounds(betas, omegas, adj, scale, rounds, compress=None):
    deg = jnp.sum(adj, axis=-1)
    return ref.dense_gossip_rounds(
        betas, omegas, adj, deg, scale, num_rounds=rounds,
        compress=compress,
    )


# ---------------------------------------------------------------------------
# Neighbor lists
# ---------------------------------------------------------------------------


def test_neighbor_lists_roundtrip():
    g = random_geometric(13, 0.5, seed=4)
    adj = _adj(g)
    idx, w, deg = ref.neighbor_lists(adj)
    assert idx.shape == w.shape and idx.dtype == jnp.int32
    V, d_max = idx.shape[1:]
    assert d_max == int((np.asarray(adj) != 0).sum(axis=-1).max())
    rebuilt = np.zeros((V, V), np.float32)
    for i in range(V):
        for s in range(d_max):
            rebuilt[i, int(idx[0, i, s])] += float(w[0, i, s])
    np.testing.assert_allclose(rebuilt, np.asarray(adj), **TOL)
    np.testing.assert_allclose(deg[0], np.asarray(adj).sum(-1), **TOL)


def test_neighbor_lists_validates_shape():
    with pytest.raises(ValueError, match="adjacencies"):
        ref.neighbor_lists(jnp.ones((3, 4)))


def test_payload_mode_validation():
    betas, omegas = _state(4, 8, 2)
    adj = _adj(build("ring", 4))
    idx, w, deg = ref.neighbor_lists(adj)
    with pytest.raises(ValueError, match="core/compression.py"):
        ref.elm_gossip_scan(
            betas, omegas, idx, w, deg, 0.1, num_rounds=2, compress="int8"
        )


# ---------------------------------------------------------------------------
# Scan fallback vs the dense round (the oracle relation)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kind,V", [("hypercube", 16), ("ring", 12), ("star", 9), ("complete", 7)]
)
def test_scan_matches_dense_rounds(kind, V):
    g = build(kind, V)
    adj = _adj(g)
    betas, omegas = _state(V, 12, 3, seed=V)
    idx, w, deg = ref.neighbor_lists(adj)
    scale = 0.5 * g.default_gamma() / V
    got = ref.elm_gossip_scan(
        betas, omegas, idx, w, deg, scale, num_rounds=7
    )
    want = _dense_rounds(betas, omegas, adj[None], scale, 7)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_scan_matches_dense_on_random_sparse_graphs(seed):
    g = random_geometric(11 + seed, 0.55, seed=seed)
    adj = _adj(g)
    V = g.num_nodes
    betas, omegas = _state(V, 10, 2, seed=seed)
    idx, w, deg = ref.neighbor_lists(adj)
    scale = 0.4 * g.default_gamma() / V
    got = ref.elm_gossip_scan(
        betas, omegas, idx, w, deg, scale, num_rounds=6
    )
    want = _dense_rounds(betas, omegas, adj[None], scale, 6)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("chunk", [1, 2, 3])
def test_chunked_scan_matches_full_gather(chunk):
    g = build("hypercube", 16)
    adj = _adj(g)
    betas, omegas = _state(16, 12, 3)
    idx, w, deg = ref.neighbor_lists(adj)
    full = ref.elm_gossip_scan(
        betas, omegas, idx, w, deg, 0.02, num_rounds=5
    )
    got = ref.elm_gossip_scan(
        betas, omegas, idx, w, deg, 0.02, num_rounds=5, chunk=chunk
    )
    np.testing.assert_allclose(got, full, **TOL)


def test_bf16_payload_matches_dense_bf16():
    g = build("hypercube", 16)
    adj = _adj(g)
    betas, omegas = _state(16, 12, 3, seed=5)
    idx, w, deg = ref.neighbor_lists(adj)
    got = ref.elm_gossip_scan(
        betas, omegas, idx, w, deg, 0.02, num_rounds=5, compress="bf16"
    )
    want = _dense_rounds(betas, omegas, adj[None], 0.02, 5, compress="bf16")
    np.testing.assert_allclose(got, want, **TOL)


def test_time_varying_snapshots_parity():
    gs = alternating_halves(12)
    adj = jnp.stack([_adj(g) for g in gs])
    betas, omegas = _state(12, 9, 2, seed=7)
    idx, w, deg = ref.neighbor_lists(adj)
    got = ref.elm_gossip_scan(
        betas, omegas, idx, w, deg, 0.1, num_rounds=5
    )
    want = _dense_rounds(betas, omegas, adj, 0.1, 5)
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret mode off-TPU)
# ---------------------------------------------------------------------------


@pytest.mark.interpret
def test_pallas_single_round_matches_reference():
    g = build("hypercube", 8)
    adj = _adj(g)
    betas, omegas = _state(8, 16, 3, seed=2)
    idx, w, deg = ref.neighbor_lists(adj)
    want = ref.gossip_round_reference(
        betas, omegas, idx[0], w[0], deg[0], 0.05
    )
    got = elm_gossip_pallas(
        betas, omegas, idx, w, deg, 0.05, num_rounds=1, block_v=4,
        interpret=True,
    )
    assert got.dtype == betas.dtype
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.interpret
@pytest.mark.parametrize("compress", [None, "bf16"])
def test_pallas_scanned_rounds_match_scan(compress):
    g = build("hypercube", 8)
    adj = _adj(g)
    betas, omegas = _state(8, 16, 3, seed=3)
    idx, w, deg = ref.neighbor_lists(adj)
    want = ref.elm_gossip_scan(
        betas, omegas, idx, w, deg, 0.05, num_rounds=4, compress=compress
    )
    got = elm_gossip_pallas(
        betas, omegas, idx, w, deg, 0.05, num_rounds=4, block_v=4,
        compress=compress, interpret=True,
    )
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.interpret
@pytest.mark.parametrize("compress", [None, "bf16"])
@pytest.mark.parametrize("V", [8, 10])  # 10: blocks of 4 and 8 pad
def test_pallas_rounds_bitwise_equal_across_blocks(V, compress):
    """Each node's update is the same code whatever the node block, so
    the block the VMEM budget picks cannot change a bit of the state."""
    adj = _adj(random_geometric(V, 0.6, seed=V))
    betas, omegas = _state(V, 16, 3, seed=7)
    idx, w, deg = ref.neighbor_lists(adj)
    outs = [
        np.asarray(elm_gossip_pallas(
            betas, omegas, idx, w, deg, 0.05, num_rounds=3, block_v=bv,
            compress=compress, interpret=True,
        ))
        for bv in (1, 4, 8)
    ]
    assert not np.array_equal(outs[0], np.asarray(betas))
    for out in outs[1:]:
        np.testing.assert_array_equal(out, outs[0])


@pytest.mark.parametrize("payload", [False, True])
def test_block_fit_at_rgg1024(payload):
    """V = 1024, L = 256, M = 10: the resident gather source alone is the
    16 MiB default scoped limit, which left one node a grid step; the
    budget the kernel may request fits blocks of 8 within v5e's 128 MiB
    VMEM, margin included."""
    V, L, M = 1024, 256, 10
    assert fit_block_v(V, L, M, 8, DEFAULT_SCOPED_VMEM, payload=payload) == 1
    bv = fit_block_v(V, L, M, 8, vmem_budget(), payload=payload)
    assert bv == 8
    need = round_vmem_bytes(V, L, M, bv, payload=payload) + 4 * 2**20
    assert need < 128 * 2**20


@pytest.mark.interpret
@pytest.mark.parametrize("compress", [None, "bf16"])
def test_pallas_multiround_arm_matches_scan(compress):
    gs = alternating_halves(8)
    adj = jnp.stack([_adj(g) for g in gs])
    betas, omegas = _state(8, 16, 3, seed=4)
    idx, w, deg = ref.neighbor_lists(adj)
    assert multiround_vmem_bytes(8, 16, 3, 2, int(idx.shape[-1])) < (
        DEFAULT_SCOPED_VMEM
    )
    want = ref.elm_gossip_scan(
        betas, omegas, idx, w, deg, 0.2, num_rounds=5, compress=compress
    )
    got = elm_gossip_pallas_multiround(
        betas, omegas, idx, w, deg, 0.2, num_rounds=5, compress=compress,
        interpret=True,
    )
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.interpret
def test_pallas_explicit_payload_round():
    g = build("hypercube", 8)
    adj = _adj(g)
    betas, omegas = _state(8, 16, 3, seed=6)
    idx, w, deg = ref.neighbor_lists(adj)
    payload = betas.astype(jnp.bfloat16).astype(jnp.float32)
    want = ref.gossip_round_payload(
        betas, payload, omegas, idx[0], w[0], deg[0], 0.05
    )
    got = elm_gossip_pallas(
        betas, omegas, idx, w, deg, 0.05, num_rounds=1, payload=payload,
        block_v=4, interpret=True,
    )
    np.testing.assert_allclose(got, want, **TOL)
    with pytest.raises(ValueError, match="payload"):
        elm_gossip_pallas(
            betas, omegas, idx, w, deg, 0.05, num_rounds=2,
            payload=payload, interpret=True,
        )


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------


def test_dispatcher_knob_cross_errors():
    g = build("hypercube", 8)
    betas, omegas = _state(8, 8, 2)
    idx, w, deg = ref.neighbor_lists(_adj(g))
    with pytest.raises(ValueError, match="block_v"):
        elm_gossip_ops.fused_gossip_rounds(
            betas, omegas, idx, w, deg, 0.1, num_rounds=2,
            use_kernel=False, block_v=4,
        )
    with pytest.raises(ValueError, match="chunk"):
        elm_gossip_ops.fused_gossip_rounds(
            betas, omegas, idx, w, deg, 0.1, num_rounds=2,
            use_kernel=True, chunk=2,
        )


def test_dispatcher_arms_agree():
    g = build("hypercube", 8)
    betas, omegas = _state(8, 16, 3, seed=9)
    idx, w, deg = ref.neighbor_lists(_adj(g))
    scan = elm_gossip_ops.fused_gossip_rounds(
        betas, omegas, idx, w, deg, 0.05, num_rounds=3, use_kernel=False
    )
    kern = elm_gossip_ops.fused_gossip_rounds(
        betas, omegas, idx, w, deg, 0.05, num_rounds=3, use_kernel=True,
        interpret=jax.default_backend() != "tpu",
    )
    np.testing.assert_allclose(scan, kern, **TOL)


def test_prefers_dense_pins():
    # the consensus grid's measured arm choices (DESIGN.md §15), pinned
    # at each backend's slack: TPU trusts the roofline ratio almost
    # directly; off-TPU the dense GEMM's efficiency edge means only
    # large V / small L points hand the round to the gather arm
    tpu = dict(slack=elm_gossip_ops.DENSE_SLACK)
    assert elm_gossip_ops.prefers_dense(16, 4, 128, 8, **tpu)
    assert not elm_gossip_ops.prefers_dense(64, 6, 128, 8, **tpu)
    assert elm_gossip_ops.prefers_dense(64, 6, 512, 8, **tpu)
    assert not elm_gossip_ops.prefers_dense(256, 8, 128, 8, **tpu)
    assert elm_gossip_ops.prefers_dense(64, 63, 128, 8, **tpu)  # complete
    cpu = dict(slack=elm_gossip_ops.DENSE_SLACK_OFF_TPU)
    assert elm_gossip_ops.prefers_dense(256, 8, 128, 8, **cpu)
    assert not elm_gossip_ops.prefers_dense(1024, 10, 128, 8, **cpu)
    assert not elm_gossip_ops.prefers_dense(256, 8, 24, 2, **cpu)
    # the default slack follows the backend
    expected = (
        elm_gossip_ops.DENSE_SLACK if jax.default_backend() == "tpu"
        else elm_gossip_ops.DENSE_SLACK_OFF_TPU
    )
    assert elm_gossip_ops.prefers_dense(
        64, 6, 128, 8
    ) == elm_gossip_ops.prefers_dense(64, 6, 128, 8, slack=expected)
    assert elm_gossip_ops.laplacian_prefers_dense(8, 7)
    assert not elm_gossip_ops.laplacian_prefers_dense(64, 6)


# ---------------------------------------------------------------------------
# NeighborMixer through the engine (composition parity)
# ---------------------------------------------------------------------------


def _engines(g, C=10.0, **kw):
    ed = engine_lib.simulated_dc_elm(g, C, **kw)
    en = engine_lib.simulated_dc_elm(g, C, mixer="neighbor", **kw)
    return ed, en


def _stream(eng, V, L, M, seed=0):
    ks = jax.random.split(jax.random.key(seed), 2)
    H = jax.random.normal(ks[0], (V, 3 * L, L), jnp.float32)
    T = jax.random.normal(ks[1], (V, 3 * L, M), jnp.float32)
    return eng.stream_init(H, T)


@pytest.mark.parametrize("compress", [None, "bf16"])
def test_neighbor_engine_matches_dense(compress):
    g = build("hypercube", 16)
    ed, en = _engines(g, compress=compress)
    st = _stream(ed, 16, 12, 2)
    gamma = en.mixer.default_gamma()
    fd, _ = ed.run(st.betas, st.omegas, gamma, 20)
    fn, _ = en.run(st.betas, st.omegas, gamma, 20)
    np.testing.assert_allclose(fn, fd, **TOL)
    assert en.wire_stats is not None
    assert en.mixer.last_wire_stats.bytes_on_wire > 0


@pytest.mark.parametrize("compress", [None, "bf16"])
def test_neighbor_engine_fused_arm_parity(compress):
    # V=256 hypercube at L=24: large V / small L, so every backend's
    # slack routes NeighborMixer.run through the fused gossip program
    # (the V=16 tests above exercise the dense-fallback arm off-TPU)
    g = build("hypercube", 256)
    assert not elm_gossip_ops.prefers_dense(256, 8, 24, 2)
    ed, en = _engines(g, compress=compress)
    st = _stream(ed, 256, 24, 2, seed=21)
    gamma = en.mixer.default_gamma()
    fd, _ = ed.run(st.betas, st.omegas, gamma, 12)
    fn, _ = en.run(st.betas, st.omegas, gamma, 12)
    np.testing.assert_allclose(fn, fd, **TOL)
    assert en.mixer.last_wire_stats.bytes_on_wire > 0


def test_neighbor_engine_fused_int8_round():
    # the explicit-payload fused round (CompressedMixer arm) at a
    # point where apply_round dispatches to the gather program
    g = build("hypercube", 256)
    spec = CompressionSpec.parse("int8")
    ed, en = _engines(g, compress=spec)
    st = _stream(engine_lib.simulated_dc_elm(g, 10.0), 256, 24, 2, seed=23)
    gamma = 0.1
    fd, _ = ed.run(st.betas, st.omegas, gamma, 6)
    fn, _ = en.run(st.betas, st.omegas, gamma, 6)
    np.testing.assert_allclose(fn, fd, **TOL)


def test_neighbor_engine_time_varying():
    gs = alternating_halves(12)
    ed, en = _engines(gs)
    st = _stream(ed, 12, 10, 2, seed=3)
    fd, _ = ed.run(st.betas, st.omegas, 0.3, 16)
    fn, _ = en.run(st.betas, st.omegas, 0.3, 16)
    np.testing.assert_allclose(fn, fd, **TOL)


def test_neighbor_engine_certified_faults():
    g = build("hypercube", 16)
    fm = FaultModel.sample_certified(g, 0.3, num_rounds=12, window=4)
    ed, en = _engines(g)
    ed = engine_lib.with_faults(ed, fm, num_rounds=12)
    en = engine_lib.with_faults(en, fm, num_rounds=12)
    # the mask fold preserved the fused mixer class on the masked period
    assert type(en.mixer._dense) is NeighborMixer
    st = _stream(engine_lib.simulated_dc_elm(g, 10.0), 16, 12, 2, seed=5)
    gamma = ed.mixer.default_gamma()
    fd, _ = ed.run(st.betas, st.omegas, gamma, 24)
    fn, _ = en.run(st.betas, st.omegas, gamma, 24)
    np.testing.assert_allclose(fn, fd, **TOL)


def test_neighbor_engine_int8_parity_and_determinism():
    g = build("hypercube", 16)
    spec = CompressionSpec.parse("int8")
    ed, en = _engines(g, compress=spec)
    st = _stream(engine_lib.simulated_dc_elm(g, 10.0), 16, 12, 2, seed=8)
    gamma = 0.2
    fd, _ = ed.run(st.betas, st.omegas, gamma, 16)
    fn, _ = en.run(st.betas, st.omegas, gamma, 16)
    np.testing.assert_allclose(fn, fd, **TOL)
    # bitwise determinism of the fused int8 arm: a fresh mixer replaying
    # the same (state, key schedule) reproduces the run exactly
    en2 = engine_lib.simulated_dc_elm(
        g, 10.0, compress=spec, mixer="neighbor"
    )
    fn2, _ = en2.run(st.betas, st.omegas, gamma, 16)
    assert bool(jnp.all(fn == fn2))


def test_churn_preserves_neighbor_mixer():
    g = build("hypercube", 16)
    en = engine_lib.simulated_dc_elm(g, 10.0, mixer="neighbor")
    st = _stream(en, 16, 10, 2, seed=11)
    e2, s2 = en.stream_leave(st, 5)
    assert type(e2.mixer) is NeighborMixer
    assert e2.mixer.num_nodes == 15
    Hn = jax.random.normal(jax.random.key(0), (30, 10), jnp.float32)
    Tn = jax.random.normal(jax.random.key(1), (30, 2), jnp.float32)
    e3, s3 = e2.stream_join(s2, Hn, Tn)
    assert type(e3.mixer) is NeighborMixer
    f3, _ = e3.run(
        s3.betas, s3.omegas, e3.mixer.default_gamma() * 0.5, 4
    )
    assert bool(jnp.all(jnp.isfinite(f3)))


def test_neighbor_mixer_generic_pytree_path():
    g = build("hypercube", 16)
    adj = _adj(g)
    nm = NeighborMixer(adj)
    dm = DenseMixer(adj)
    tree = {
        "a": jax.random.normal(jax.random.key(2), (16, 7), jnp.float32),
        "b": jax.random.normal(jax.random.key(3), (16, 3, 2), jnp.float32),
    }
    rule = engine_lib.AverageRule()
    o1, _ = nm.run(rule, tree, None, 0.1, 6)
    o2, _ = dm.run(rule, tree, None, 0.1, 6)
    for k in tree:
        np.testing.assert_allclose(o1[k], o2[k], **TOL)


def test_dense_mixer_precomputed_degrees():
    gs = alternating_halves(10)
    adj = jnp.stack([_adj(g) for g in gs])
    dm = DenseMixer(adj)
    assert dm.degrees.shape == (2, 10)
    np.testing.assert_allclose(
        dm.degrees, jnp.sum(adj, axis=-1), **TOL
    )
    np.testing.assert_allclose(dm._degree_row(3), dm.degrees[1], **TOL)


def test_compress_payload_rejects_unknown_mode():
    # satellite pin: the inline knob names the CompressionSpec escape
    # hatch for richer wire formats
    from repro.core.mixers import compress_payload

    with pytest.raises(ValueError, match="CompressionSpec"):
        compress_payload(jnp.ones((2, 2)), "int8")


# ---------------------------------------------------------------------------
# Hypothesis property sweep (skipped when hypothesis is unavailable —
# the deterministic parametrized parity pins above always run)
# ---------------------------------------------------------------------------

_hyp = pytest.importorskip  # alias so the guard reads as intent


def test_property_fused_round_matches_dense():
    _hyp("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(
        v=st.integers(4, 14),
        l=st.integers(2, 10),
        m=st.integers(1, 3),
        radius=st.floats(0.45, 0.8),
        seed=st.integers(0, 2**16),
    )
    @settings(max_examples=15, deadline=None)
    def prop(v, l, m, radius, seed):  # noqa: E741
        g = random_geometric(v, radius, seed=seed % 100)
        adj = _adj(g)
        betas, omegas = _state(v, l, m, seed=seed)
        idx, w, deg = ref.neighbor_lists(adj)
        scale = 0.3 * g.default_gamma() / v
        got = ref.elm_gossip_scan(
            betas, omegas, idx, w, deg, scale, num_rounds=3
        )
        want = _dense_rounds(betas, omegas, adj[None], scale, 3)
        np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-5)

    prop()
