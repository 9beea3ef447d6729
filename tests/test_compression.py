"""Compressed gossip (paper Sec. V: "reduction of the amount of
information exchanging").

Two layers under test:

* the legacy inline ``compress="bf16"`` mixer knob — pins (1)
  compressed-vs-uncompressed drift on the dense path, (2)
  DenseMixer-vs-PpermuteMixer agreement under compression, and (3)
  that compressed runs still satisfy the Thm. 2 stability bound
  gamma < 1/d_max;

* the ``core/compression.py`` subsystem (``CompressionSpec`` +
  ``CompressedMixer``) — int8 round-trip edges (all-zero / rank-1 /
  ragged-tile payloads), CHOCO error feedback cancelling quantization
  bias over rounds (hypothesis property + a deterministic pin),
  dense == ppermute under int8 within a pinned tolerance (incl.
  composed with a fault trace), event-triggered skipping, exact
  bytes-on-wire accounting, and uniform None/"none" handling across
  mixers and engine constructors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, consensus, dc_elm, engine, mixers
from repro.core.compression import CompressionSpec
from tests.conftest import run_py


def _problem(V=8, Ni=32, L=12, M=2, seed=0):
    kx, kt = jax.random.split(jax.random.key(seed))
    H = jax.random.normal(kx, (V, Ni, L)) / np.sqrt(L)
    T = jax.random.normal(kt, (V, Ni, M))
    return H, T


def test_dense_bf16_close_to_fp32():
    H, T = _problem()
    C = 0.5
    g = consensus.hypercube(3)
    state, P_, Q_ = dc_elm.simulate_init(H, T, C)
    gamma = g.default_gamma()
    full, _ = engine.simulated_dc_elm(g, C).run(
        state.betas, state.omegas, gamma, 200
    )
    comp, _ = engine.simulated_dc_elm(g, C, compress="bf16").run(
        state.betas, state.omegas, gamma, 200
    )
    # pinned: observed drift ~1.2e-3 at 200 rounds on unit-scale betas
    np.testing.assert_allclose(comp, full, atol=5e-3)
    beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
    assert float(dc_elm.distance_to(comp, beta_star)) < 0.01


def test_bf16_respects_gamma_stability_bound():
    """At gamma = 0.99/d_max (just inside the Thm. 2 bound) the
    compressed iteration still contracts: disagreement decays
    monotonically to the quantization floor instead of diverging."""
    H, T = _problem(seed=3)
    C = 0.5
    g = consensus.hypercube(3)
    state, P_, Q_ = dc_elm.simulate_init(H, T, C)
    beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
    gamma = 0.99 * g.gamma_upper_bound()
    eng = engine.simulated_dc_elm(g, C, compress="bf16")
    betas, traces = eng.run(
        state.betas, state.omegas, gamma, 1000,
        trace_fn=dc_elm.consensus_error,
    )
    traces = np.asarray(traces)
    assert traces[-1] < 5e-3  # reached the bf16 consensus floor
    # no blow-up anywhere along the run, and early rounds contract
    assert traces.max() <= traces[0] * 1.01
    assert traces[200] < traces[0] / 10
    assert float(dc_elm.distance_to(betas, beta_star)) < 0.01


def test_dense_vs_ppermute_bf16_agree():
    """Compressed rounds on the two mixers agree within a pinned
    tolerance (both quantize the payload to bf16; the dense path
    accumulates the Laplacian in f32, the gossip path in bf16)."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import dc_elm, engine, gossip
from repro.utils import compat
V, Ni, L, M, C = 8, 32, 12, 2, 0.5
mesh = compat.make_mesh((8,), ('data',))
kx, kt = jax.random.split(jax.random.key(0))
H = jax.random.normal(kx, (V, Ni, L)) / np.sqrt(L)
T = jax.random.normal(kt, (V, Ni, M))
state, P_, Q_ = dc_elm.simulate_init(H, T, C)
beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
for kind in ['ring', 'hypercube']:
    spec = gossip.GossipSpec(axes=('data',), kinds=(kind,))
    g = spec.to_graph({'data': V})
    gamma = g.default_gamma()
    dense, _ = engine.simulated_dc_elm(g, C, compress='bf16').run(
        state.betas, state.omegas, gamma, 400)
    shard, _ = engine.sharded_dc_elm(mesh, spec, C, compress='bf16').run(
        state.betas, state.omegas, gamma, 400)
    # pinned: observed ~5e-4 max divergence at 400 rounds
    assert np.allclose(dense, shard, atol=2e-3), (
        kind, np.abs(np.asarray(dense) - np.asarray(shard)).max())
    assert float(dc_elm.distance_to(shard, beta_star)) < 0.01, kind
print('OK')
"""
    r = run_py(code, devices=8)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# core/compression.py: int8 round-trip edges
# ---------------------------------------------------------------------------


def test_int8_roundtrip_all_zero_is_exact():
    """Scale-0 tiles must encode the zero code, not NaN/garbage."""
    flat = jnp.zeros((200,))
    out = compression.int8_roundtrip(flat, tile=64, key=jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(out), np.zeros(200))


def test_int8_roundtrip_rank1_error_bounded():
    """Per-element error is below one quantization step of its tile,
    including on a payload whose length is not a tile multiple."""
    u = jnp.linspace(-1.0, 1.0, 20)
    v = jnp.linspace(0.1, 2.0, 5)
    flat = jnp.outer(u, v).reshape(-1)  # 100 values, tile 64 -> ragged
    out = compression.int8_roundtrip(flat, tile=64, key=jax.random.key(1))
    err = np.abs(np.asarray(out) - np.asarray(flat))
    t = np.abs(np.asarray(jnp.pad(flat, (0, 28)).reshape(2, 64)))
    step = t.max(axis=1) / 127.0
    assert err[:64].max() <= step[0] + 1e-7
    assert err[64:].max() <= step[1] + 1e-7


def test_int8_roundtrip_unbiased():
    """Stochastic rounding is unbiased: averaging many independent
    encodes recovers the value to ~1/sqrt(n) of a step."""
    flat = jnp.full((64,), 0.3141)
    outs = jnp.stack([
        compression.int8_roundtrip(flat, 64, jax.random.key(s))
        for s in range(200)
    ])
    step = 0.3141 / 127.0
    assert abs(float(outs.mean()) - 0.3141) < 0.2 * step


def test_topk_keeps_largest_and_zeroes_rest():
    flat = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05, 0.0])
    out = np.asarray(compression.topk_roundtrip(flat, 2))
    np.testing.assert_allclose(out, [0.0, -5.0, 0.0, 3.0, 0.0, 0.0])


# ---------------------------------------------------------------------------
# error feedback cancels quantization bias
# ---------------------------------------------------------------------------


def _replica_residual(x, rounds, *, feedback, tile=32, seed=0):
    """||x - xhat|| after `rounds` of the replica protocol on a fixed
    target: xhat += Q(x - xhat) (feedback) vs xhat = Q(x) (ablation)."""
    xhat = jnp.zeros_like(x)
    for k in range(rounds):
        key = jax.random.fold_in(jax.random.key(seed), k)
        if feedback:
            xhat = xhat + compression.int8_roundtrip(x - xhat, tile, key)
        else:
            xhat = compression.int8_roundtrip(x, tile, key)
    return float(jnp.max(jnp.abs(x - xhat)))


def test_error_feedback_cancels_quantization_bias():
    """Deterministic pin of the hypothesis property below: the EF
    residual contracts geometrically (each round quantizes a payload
    ~127x smaller), while the memoryless ablation stays at one step."""
    x = jax.random.normal(jax.random.key(3), (96,))
    step = float(jnp.abs(x).max()) / 127.0
    ef = _replica_residual(x, 8, feedback=True)
    raw = _replica_residual(x, 8, feedback=False)
    assert ef < 1e-10  # (1/127)^8-ish of the initial scale
    assert raw > 0.01 * step  # ablation is stuck at the quant floor


try:
    from hypothesis import given, settings, strategies as st

    @given(
        n=st.integers(3, 200),
        tile=st.integers(1, 64),
        scale=st.floats(1e-3, 1e3),
        seed=st.integers(0, 2**30),
    )
    @settings(max_examples=25, deadline=None)
    def test_error_feedback_contracts_property(n, tile, scale, seed):
        x = scale * jax.random.normal(jax.random.key(seed), (n,))
        r0 = float(jnp.abs(x).max())
        ef = _replica_residual(x, 6, feedback=True, tile=tile, seed=seed)
        # six EF rounds contract the residual far below one first-round
        # quantization step (the memoryless floor)
        assert ef <= r0 / 127.0 * 0.2 + 1e-12
except ImportError:  # hypothesis is an optional dev dependency
    pass


# ---------------------------------------------------------------------------
# compressed consensus: convergence, event triggering, wire accounting
# ---------------------------------------------------------------------------


def _problem_big(V=8, Ni=32, L=32, M=4, seed=0):
    kx, kt = jax.random.split(jax.random.key(seed))
    H = jax.random.normal(kx, (V, Ni, L)) / np.sqrt(L)
    T = jax.random.normal(kt, (V, Ni, M))
    return H, T


def test_int8_ef_matches_fp32_convergence():
    """int8 + replica error feedback has no quantization floor: it
    reaches the fp32 run's residual class, while the memoryless
    ablation (error_feedback=False) is stuck well above it."""
    H, T = _problem_big()
    C = 0.5
    g = consensus.hypercube(3)
    state, P_, Q_ = dc_elm.simulate_init(H, T, C)
    beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
    gamma = g.default_gamma()
    runs = {}
    for name, spec in [
        ("fp32", None),
        ("int8", CompressionSpec(mode="int8", tile=128)),
        ("noef", CompressionSpec(mode="int8", tile=128,
                                 error_feedback=False)),
    ]:
        eng = engine.simulated_dc_elm(g, C, compress=spec)
        betas, _ = eng.run(state.betas, state.omegas, gamma, 400)
        runs[name] = float(dc_elm.distance_to(betas, beta_star))
    assert runs["int8"] < 10 * max(runs["fp32"], 1e-7)
    assert runs["noef"] > 100 * runs["int8"]


def test_event_triggered_skips_links_and_converges():
    H, T = _problem_big(seed=5)
    C = 0.5
    g = consensus.hypercube(3)
    state, P_, Q_ = dc_elm.simulate_init(H, T, C)
    beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
    spec = CompressionSpec(mode="int8", tile=128, event_threshold=1e-3)
    eng = engine.simulated_dc_elm(g, C, compress=spec)
    betas, _ = eng.run(state.betas, state.omegas, g.default_gamma(), 1500)
    ws = eng.wire_stats
    assert float(dc_elm.distance_to(betas, beta_star)) < 1e-2
    assert ws.links_skipped > 0.3 * ws.links_live
    assert ws.compression_ratio < 0.25
    assert ws.links_sent + ws.links_skipped == ws.links_live


def test_wire_stats_exact_accounting():
    """bytes = live directed links x per-message bytes, on all mixers."""
    H, T = _problem_big()
    C, rounds = 0.5, 17
    g = consensus.hypercube(3)
    state, _, _ = dc_elm.simulate_init(H, T, C)
    V, L, M = 8, 32, 4
    links = int((np.asarray(g.adjacency) > 0).sum())  # directed, per round

    eng = engine.simulated_dc_elm(g, C)
    eng.run(state.betas, state.omegas, g.default_gamma(), rounds)
    ws = eng.wire_stats
    assert ws.links_live == rounds * links
    assert ws.bytes_on_wire == rounds * links * L * M * 4
    assert ws.per_round_bytes.shape == (rounds,)

    spec = CompressionSpec(mode="int8", tile=128)
    eng8 = engine.simulated_dc_elm(g, C, compress=spec)
    eng8.run(state.betas, state.omegas, g.default_gamma(), rounds)
    msg = L * M + 4 * ((L * M + 127) // 128)  # codes + per-tile scales
    assert eng8.wire_stats.bytes_on_wire == rounds * links * msg
    assert eng8.wire_stats.bytes_uncompressed == ws.bytes_on_wire

    # composed with a fault trace: only live links move bytes
    keep = consensus.FaultModel(
        graph=g, crashes=(consensus.NodeCrash(node=1, start=0,
                                              duration=rounds),)
    ).edge_keep(rounds)
    engf = engine.with_faults(engine.simulated_dc_elm(g, C, compress=spec),
                              keep)
    engf.run(state.betas, state.omegas, g.default_gamma(), rounds)
    live = int((keep * np.asarray(g.adjacency)[None] > 0).sum())
    assert engf.wire_stats.links_live == live
    assert engf.wire_stats.bytes_on_wire == live * msg


def test_stream_chunk_threads_wire_stats():
    H, T = _problem_big()
    C = 0.5
    g = consensus.hypercube(3)
    spec = CompressionSpec(mode="int8", tile=128)
    eng = engine.simulated_dc_elm(g, C, compress=spec)
    st0 = eng.stream_init(H, T)
    assert eng.mixer.last_wire_stats is None  # no rounds yet
    dH = jax.random.normal(jax.random.key(9), (8, 4, 32)) / np.sqrt(32)
    dT = jax.random.normal(jax.random.key(10), (8, 4, 4))
    eng.stream_chunk(st0, added=(dH, dT), gamma=g.default_gamma(),
                     num_iters=12)
    ws = eng.wire_stats
    assert ws is not None and ws.rounds == 12
    assert eng.mixer.last_wire_stats is ws
    assert ws.bytes_on_wire == int(ws.per_round_bytes.sum()) > 0


# ---------------------------------------------------------------------------
# unknown modes and None/"none" uniformity
# ---------------------------------------------------------------------------


def test_compress_payload_unknown_mode_message():
    x = jnp.ones((4, 4))
    with pytest.raises(ValueError) as ei:
        mixers.compress_payload(x, "int4")
    msg = str(ei.value)
    assert "int4" in msg and "bf16" in msg
    assert "CompressionSpec" in msg  # points at the richer subsystem


def test_unknown_modes_fail_at_construction():
    adj = jnp.asarray(np.asarray(consensus.ring(8).adjacency))
    with pytest.raises(ValueError, match="unknown gossip compression"):
        mixers.DenseMixer(adj, compress="int4")
    with pytest.raises(ValueError, match="unknown compression mode"):
        CompressionSpec(mode="int4")
    with pytest.raises(ValueError, match="unknown compression mode"):
        engine.simulated_dc_elm(consensus.ring(8), 0.5, compress="int4")
    with pytest.raises(TypeError, match="CompressionSpec"):
        CompressionSpec.parse(3.14)
    # event triggering needs the replica memory; without it every round
    # is an absolute broadcast and the threshold would silently no-op
    with pytest.raises(ValueError, match="event_threshold"):
        CompressionSpec(mode="int8", error_feedback=False,
                        event_threshold=1e-3)


def test_none_and_none_string_are_uniform():
    """None and "none" mean "no compression" everywhere."""
    g = consensus.ring(8)
    adj = jnp.asarray(np.asarray(g.adjacency))
    assert mixers.DenseMixer(adj, compress="none").compress is None
    assert mixers.DenseMixer(adj, compress=None).compress is None
    pm = mixers.PpermuteMixer(
        spec=engine.gossip.GossipSpec(axes=("data",), kinds=("ring",)),
        axis_sizes={"data": 8}, compress="none",
    )
    assert pm.compress is None
    x = jnp.ones((8, 3))
    np.testing.assert_array_equal(
        np.asarray(mixers.compress_payload(x, None)),
        np.asarray(mixers.compress_payload(x, "none")),
    )
    assert CompressionSpec.parse(None).is_identity
    assert CompressionSpec.parse("none").is_identity
    H, T = _problem_big()
    state, _, _ = dc_elm.simulate_init(H, T, 0.5)
    outs = []
    for c in (None, "none"):
        eng = engine.simulated_dc_elm(consensus.hypercube(3), 0.5,
                                      compress=c)
        betas, _ = eng.run(state.betas, state.omegas, 0.1, 20)
        outs.append(np.asarray(betas))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# dense == ppermute under int8 (plus fault composition), pinned
# ---------------------------------------------------------------------------


def test_dense_vs_ppermute_int8_agree():
    """The two substrates quantize identically (same per-(round, node)
    PRNG stream) and agree within a pinned tolerance, with and without
    a composed fault trace; wire accounting is byte-identical."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import consensus, dc_elm, engine, gossip
from repro.core.compression import CompressionSpec
from repro.utils import compat
V, Ni, L, M, C = 8, 32, 32, 4, 0.5
mesh = compat.make_mesh((8,), ('data',))
kx, kt = jax.random.split(jax.random.key(0))
H = jax.random.normal(kx, (V, Ni, L)) / np.sqrt(L)
T = jax.random.normal(kt, (V, Ni, M))
state, P_, Q_ = dc_elm.simulate_init(H, T, C)
beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
spec = gossip.GossipSpec(axes=('data',), kinds=('hypercube',))
g = spec.to_graph({'data': V})
gamma = g.default_gamma()
cs = CompressionSpec(mode='int8', tile=128)
de = engine.simulated_dc_elm(g, C, compress=cs)
dense, _ = de.run(state.betas, state.omegas, gamma, 400)
se = engine.sharded_dc_elm(mesh, spec, C, compress=cs)
shard, _ = se.run(state.betas, state.omegas, gamma, 400)
# pinned: observed ~1e-6 max divergence at 400 rounds
assert np.allclose(dense, shard, atol=1e-4), np.abs(
    np.asarray(dense) - np.asarray(shard)).max()
assert float(dc_elm.distance_to(shard, beta_star)) < 1e-3
assert de.wire_stats.bytes_on_wire == se.wire_stats.bytes_on_wire
fm = consensus.FaultModel.sample_certified(g, 0.2, num_rounds=64, window=16)
keep = fm.edge_keep(64)
df = engine.with_faults(engine.simulated_dc_elm(g, C, compress=cs), keep)
sf = engine.with_faults(engine.sharded_dc_elm(mesh, spec, C, compress=cs), keep)
assert type(df.mixer).__name__ == 'CompressedMixer'  # compression outermost
d2, _ = df.run(state.betas, state.omegas, gamma, 400)
s2, _ = sf.run(state.betas, state.omegas, gamma, 400)
assert np.allclose(d2, s2, atol=1e-4), np.abs(
    np.asarray(d2) - np.asarray(s2)).max()
assert float(dc_elm.distance_to(s2, beta_star)) < 1e-3
assert df.wire_stats.bytes_on_wire == sf.wire_stats.bytes_on_wire
assert df.wire_stats.links_live == sf.wire_stats.links_live
# program cache: a second run with new masks of the same period reuses
n0 = len(sf.mixer._programs)
sf.run(state.betas, state.omegas, gamma, 400)
assert len(sf.mixer._programs) == n0
print('OK')
"""
    r = run_py(code, devices=8)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout


def test_topk_ties_keep_exactly_count():
    out = np.asarray(compression.topk_roundtrip(jnp.ones(6), 2))
    assert (out != 0).sum() == 2  # billing matches the kept set


def test_blocked_runs_continue_replica_state():
    """Replica memory and the absolute round counter persist across
    run() calls on one mixer: N blocked runs are bitwise-identical to
    one contiguous run (same PRNG / fault-trace / refresh streams,
    same wire bytes), and reset_replicas() cold-starts."""
    H, T = _problem_big()
    C = 0.5
    g = consensus.hypercube(3)
    state, _, _ = dc_elm.simulate_init(H, T, C)
    gamma = g.default_gamma()
    spec = CompressionSpec(mode="int8", tile=128, event_threshold=1e-3)
    keep = consensus.FaultModel.sample_certified(
        g, 0.2, num_rounds=64, window=16
    ).edge_keep(64)

    blocked = engine.with_faults(
        engine.simulated_dc_elm(g, C, compress=spec), keep
    )
    b1, tot = state.betas, 0
    for _ in range(4):
        b1, _ = blocked.run(b1, state.omegas, gamma, 50)
        tot += blocked.wire_stats.bytes_on_wire
    contiguous = engine.with_faults(
        engine.simulated_dc_elm(g, C, compress=spec), keep
    )
    b2, _ = contiguous.run(state.betas, state.omegas, gamma, 200)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    assert tot == contiguous.wire_stats.bytes_on_wire
    blocked.mixer.reset_replicas()
    b3, _ = blocked.run(state.betas, state.omegas, gamma, 200)
    np.testing.assert_array_equal(np.asarray(b3), np.asarray(b2))
