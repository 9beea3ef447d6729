"""Microbenchmarks: kernels, online updates, communication models.

Runnable standalone for a single profile:

  PYTHONPATH=src python -m benchmarks.micro --profile stats

prints the fused feature->moment pipeline's FLOP utilization next to
the existing gram numbers (``--profile`` accepts any registered name;
``benchmarks.run`` remains the multi-suite entry point).
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import consensus, dc_elm, engine, gossip, incremental, online
from repro.kernels.gram import gram_pallas
from repro.kernels.gram_ref import gram_reference
from repro.kernels.ssd_ref import ssd_reference


def _timeit_us(fn, *args, repeats=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e6


def bench_gram():
    """Paper hot-spot P = H^T H: oracle timing + kernel flop accounting."""
    rows = []
    for (N, L) in [(2048, 128), (8192, 256), (4096, 512)]:
        H = jax.random.normal(jax.random.key(0), (N, L), jnp.float32)
        ref = jax.jit(gram_reference)
        us = _timeit_us(ref, H)
        flops = 2 * N * L * L
        rows.append((
            f"kernels/gram_ref_N{N}_L{L}", us,
            f"gflops={flops/us/1e3:.2f}",
        ))
        # interpret-mode kernel: correctness-checked, not a CPU perf path
        out = gram_pallas(H[:256], interpret=True, block_l=64, block_n=128)
        err = float(jnp.max(jnp.abs(out - gram_reference(H[:256]))))
        rows.append((f"kernels/gram_pallas_interp_N256_L{L}", 0.0,
                     f"max_err={err:.2e}"))
    return rows, {}


def bench_stats_profile():
    """Fused feature->moment FLOP utilization next to the gram numbers.

    The fused pipeline does the gram work *plus* the feature matmul and
    activation in the same streaming pass, so its gflops row is
    directly comparable to kernels/gram_ref at the same (N, L): the
    utilization the statistics plane sustains on the full Algorithm 1
    steps 1-3, not just the moment contraction. Includes an
    interpret-mode correctness row for the Pallas kernel, mirroring
    bench_gram's, and a tuned-vs-default comparison row showing what
    the autotuned cache (kernels/autotune.py) buys over the hard-coded
    block config at each point.
    """
    from repro.core import features, stats
    from repro.kernels import autotune, elm_stats_ops
    from repro.kernels.elm_stats import elm_stats_pallas

    rows = list(bench_gram()[0])  # the gram numbers, for side-by-side
    D, M = 64, 8
    # measure exactly what production dispatches on this backend
    impl = "pallas" if jax.default_backend() == "tpu" else "scan"
    fused = jax.jit(
        lambda X, W, b, T: elm_stats_ops.fused_moments(
            X, W, b, T, activation="sigmoid", block_n=2048
        )
    )
    for (N, L) in [(2048, 128), (8192, 256), (4096, 512)]:
        ks = jax.random.split(jax.random.key(0), 4)
        X = jax.random.normal(ks[0], (N, D), jnp.float32)
        W = jax.random.normal(ks[1], (D, L), jnp.float32)
        b = jax.random.normal(ks[2], (L,), jnp.float32)
        T = jax.random.normal(ks[3], (N, M), jnp.float32)
        us = _timeit_us(fused, X, W, b, T)
        flops = 2 * N * D * L + 2 * N * L * (L + M)
        rows.append((
            f"kernels/elm_stats_{impl}_N{N}_L{L}", us,
            f"gflops={flops/us/1e3:.2f};fused=feature+gram+cross",
        ))
        # tuned-vs-default: the cache's config (nearest-N fallback
        # included) against the hard-coded default at the same point
        point = autotune.TunePoint(
            op="stats", impl=impl, N=N, D=D, L=L, M=M,
            dtype="float32", backend=jax.default_backend(),
        )
        default_cfg = {
            k: min(v, N if k != "block_l" else L)
            for k, v in autotune.DEFAULTS[("stats", impl)].items()
        }
        tuned_cfg = autotune.lookup("stats", N, D, L, M, "float32", impl=impl)
        if tuned_cfg is None or tuned_cfg == default_cfg:
            rows.append((
                f"kernels/elm_stats_tuned_N{N}_L{L}", 0.0,
                "tuned=default (cache miss or same config)",
            ))
        else:
            us_d = _timeit_us(autotune.candidate_fn(point, default_cfg),
                              X, W, b, T)
            us_t = _timeit_us(autotune.candidate_fn(point, tuned_cfg),
                              X, W, b, T)
            cfg_s = ",".join(f"{k}={v}" for k, v in sorted(tuned_cfg.items()))
            rows.append((
                f"kernels/elm_stats_tuned_N{N}_L{L}", us_t,
                f"tuned({cfg_s})_speedup={us_d / max(us_t, 1e-9):.2f}x"
                f";default_us={us_d:.0f}",
            ))
    # interpret-mode kernel correctness row (vs the statistics plane)
    fmap = features.make_random_features(jax.random.key(1), D, 64)
    X = jax.random.normal(jax.random.key(2), (256, D))
    T = jax.random.normal(jax.random.key(3), (256, M))
    W, b, act = stats.fusable_params(fmap)
    P1, Q1 = elm_stats_pallas(
        X, W, b, T, activation=act, interpret=True, block_l=32, block_n=64
    )
    ref = stats.from_raw(X, T, fmap, use_kernel=False)
    err = max(
        float(jnp.max(jnp.abs(P1 - ref.P))), float(jnp.max(jnp.abs(Q1 - ref.Q)))
    )
    rows.append((
        "kernels/elm_stats_pallas_interp_N256_L64", 0.0, f"max_err={err:.2e}"
    ))
    return rows, {}


def bench_ssd():
    rows = []
    for (b, s, nh, hd, ds) in [(4, 512, 8, 64, 64), (2, 1024, 16, 64, 128)]:
        ks = jax.random.split(jax.random.key(1), 5)
        x = jax.random.normal(ks[0], (b, s, nh, hd))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
        A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
        B = jax.random.normal(ks[3], (b, s, ds))
        C = jax.random.normal(ks[4], (b, s, ds))
        fn = jax.jit(lambda *a: ssd_reference(*a, chunk=128)[0])
        us = _timeit_us(fn, x, dt, A, B, C)
        toks = b * s
        rows.append((f"kernels/ssd_ref_b{b}_s{s}", us,
                     f"tokens_per_s={toks/us*1e6:.0f}"))
    return rows, {}


def bench_attention():
    rows = []
    from repro.models.attention import flash_attention

    for (B, S, K, G, hd) in [(2, 1024, 4, 2, 64), (1, 4096, 2, 4, 64)]:
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (B, S, K, G, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
        pos = jnp.arange(S)
        fn = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, q_positions=pos, k_positions=pos, causal=True
            )
        )
        us = _timeit_us(fn, q, k, v)
        flops = 2 * 2 * B * K * G * S * S * hd / 2  # causal half
        rows.append((f"kernels/flash_jnp_B{B}_S{S}", us,
                     f"gflops={flops/us/1e3:.2f}"))
    return rows, {}


def bench_online_vs_direct():
    """Algorithm 2's claim: Woodbury chunk update beats O(L^3) recompute."""
    rows = []
    for L, n, dn in [(256, 4096, 64), (512, 8192, 64), (1024, 8192, 128)]:
        ks = jax.random.split(jax.random.key(3), 4)
        H = jax.random.normal(ks[0], (n, L)) / np.sqrt(L)
        T = jax.random.normal(ks[1], (n, 4))
        dH = jax.random.normal(ks[2], (dn, L)) / np.sqrt(L)
        dT = jax.random.normal(ks[3], (dn, 4))
        st = online.init_state(H, T, C=8.0, V=4)
        add = jax.jit(online.add_chunk)
        us_add = _timeit_us(add, st, dH, dT)
        direct = jax.jit(
            lambda H, T: online.init_state(H, T, 8.0, 4),
        )
        H2 = jnp.concatenate([H, dH])
        T2 = jnp.concatenate([T, dT])
        us_direct = _timeit_us(direct, H2, T2)
        rows.append((
            f"online/woodbury_L{L}_dn{dn}", us_add,
            f"direct_us={us_direct:.0f};speedup={us_direct/us_add:.1f}x",
        ))
    return rows, {}


def bench_consensus_vs_incremental():
    """Paper Sec. II-B: gossip vs Hamiltonian-cycle, latency-normalized.

    Latency model: one gossip round = 1 parallel neighbor exchange; one
    incremental cycle = V *sequential* hops. At an equal hop-latency
    budget we compare achieved distance to the centralized solution.
    The paper's structural claims (no NP-hard cycle construction, no
    single point of failure) are qualitative and noted in EXPERIMENTS.md.
    """
    rows = []
    V, Ni, L, M, C = 8, 64, 16, 2, 0.5
    ks = jax.random.split(jax.random.key(4), 2)
    H = jax.random.normal(ks[0], (V, Ni, L))
    T = jax.random.normal(ks[1], (V, Ni, M))
    state, P_, Q_ = dc_elm.simulate_init(H, T, C)
    beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
    budget_hops = 2000
    g = consensus.complete(V)  # all-neighbor exchange, 1 hop latency
    eng = engine.simulated_dc_elm(g, C)
    betas, _ = eng.run(
        state.betas, state.omegas, g.default_gamma(), budget_hops
    )
    d_dc = float(dc_elm.distance_to(betas, beta_star))
    z, _ = incremental.run(
        P_, Q_, alpha=2e-4, C=C, num_cycles=budget_hops // V
    )
    den = 1 + float(jnp.linalg.norm(beta_star))
    d_inc = float(jnp.linalg.norm(z - beta_star)) / den
    rows.append((
        f"comm/dcelm_complete{V}", 0.0,
        f"hops={budget_hops};dist={d_dc:.4f};spof=none;cycle_required=no",
    ))
    rows.append((
        f"comm/incremental_cycle{V}", 0.0,
        f"hops={budget_hops};cycles={budget_hops // V};dist={d_inc:.4f};"
        f"spof=any_node;cycle_required=yes(NP-hard)",
    ))
    spec = gossip.GossipSpec(axes=("data",), kinds=("ring",))
    payload = L * M * 4
    rows.append((
        "comm/bytes_per_round", 0.0,
        f"dcelm_ring={gossip.collective_bytes_per_round(spec, {'data': V}, payload)}"
        f";incremental_per_cycle={payload * V}",
    ))
    return rows, {}


def bench_streaming_driver():
    """Algorithm 2 end-to-end through the engine: one chunk event
    (Woodbury add+remove, re-seed, K rounds) vs recompute-from-scratch
    (O(L^3) per-node re-inversion, then the same K rounds)."""
    rows = []
    K = 50
    for V, L, n, dn in [(4, 256, 4096, 64), (8, 512, 4096, 128)]:
        M, C = 4, 8.0
        g = consensus.ring(V)
        ks = jax.random.split(jax.random.key(6), 4)
        H = jax.random.normal(ks[0], (V, n, L)) / np.sqrt(L)
        T = jax.random.normal(ks[1], (V, n, M))
        dH = jax.random.normal(ks[2], (V, dn, L)) / np.sqrt(L)
        dT = jax.random.normal(ks[3], (V, dn, M))
        eng = engine.simulated_dc_elm(g, C)
        state = eng.stream_init(H, T)
        gamma = g.default_gamma()

        @jax.jit
        def chunk_event(s):
            s2, _ = eng.stream_chunk(
                s, added=(dH, dT), removed=(H[:, :dn], T[:, :dn]),
                gamma=gamma, num_iters=K,
            )
            return s2.betas

        us_stream = _timeit_us(chunk_event, state)

        H2 = jnp.concatenate([H[:, dn:], dH], axis=1)
        T2 = jnp.concatenate([T[:, dn:], dT], axis=1)

        @jax.jit
        def recompute(H2, T2):
            s = eng.stream_init(H2, T2)
            betas, _ = eng.run(s.betas, s.omegas, gamma, K)
            return betas

        us_direct = _timeit_us(recompute, H2, T2)
        rows.append((
            f"streaming/engine_V{V}_L{L}_dn{dn}_K{K}", us_stream,
            f"recompute_us={us_direct:.0f};"
            f"speedup={us_direct/us_stream:.1f}x",
        ))
    return rows, {}


def bench_fault_tolerance(rounds: int = 4000, tol: float = 1e-2):
    """Robustness: rounds-to-tolerance and ICI bytes vs link failure rate.

    DC-ELM under per-round Bernoulli edge dropout on a certified
    jointly connected trace (FaultModel + FaultyMixer). Collective
    bytes count only *live* links — a dropped link moves no payload —
    so the scheme trades rounds for bytes gracefully. The fusion-center
    baseline has no such trade: any node crash stalls its all-reduce
    for the whole outage (stall row below).
    """
    rows = []
    V, Ni, L, M, C = 16, 48, 12, 1, 0.05
    ks = jax.random.split(jax.random.key(7), 2)
    H = jax.random.normal(ks[0], (V, Ni, L))
    T = jax.random.normal(ks[1], (V, Ni, M))
    state, P_, Q_ = dc_elm.simulate_init(H, T, C)
    beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
    g = consensus.build("hypercube", V)
    gamma = g.default_gamma()
    payload = L * M * 4
    trace_fn = lambda betas: dc_elm.distance_to(betas, beta_star)  # noqa: E731
    window = 16
    for p in [0.0, 0.1, 0.2, 0.3, 0.4]:
        fm = consensus.FaultModel.sample_certified(
            g, p, num_rounds=rounds, window=window
        )
        keep = fm.edge_keep(rounds)
        eng = engine.with_faults(engine.simulated_dc_elm(g, C), keep)
        _, traces = eng.run(state.betas, state.omegas, gamma, rounds,
                            trace_fn=trace_fn)
        traces = np.asarray(traces)
        hit = np.nonzero(traces < tol)[0]
        r2t = int(hit[0]) + 1 if hit.size else -1
        # bytes actually moved: one payload per live directed edge
        live = keep.sum(axis=(1, 2))  # directed live edges per round
        total_edges = float((g.adjacency > 0).sum())
        upto = r2t if r2t > 0 else rounds
        bytes_per_node = float(live[:upto].sum()) * payload / V
        rows.append((
            f"faults/bernoulli_p{p:.1f}", 0.0,
            f"rounds_to_{tol:g}={r2t};bytes_per_node={bytes_per_node:.0f};"
            f"live_edge_frac={live.mean() / total_edges:.2f};"
            f"certified_window={window}",
        ))
    # node crash/rejoin burst: DC-ELM degrades, fusion stalls outright
    crash = consensus.NodeCrash(node=3, start=200, duration=400)
    fm = consensus.FaultModel(graph=g, crashes=(crash,))
    eng = engine.with_faults(engine.simulated_dc_elm(g, C), fm.edge_keep(rounds))
    _, traces = eng.run(state.betas, state.omegas, gamma, rounds,
                        trace_fn=trace_fn)
    traces = np.asarray(traces)
    hit = np.nonzero(traces < tol)[0]
    r2t = int(hit[0]) + 1 if hit.size else -1
    stall = crash.duration
    rows.append((
        "faults/crash_rejoin_node3", 0.0,
        f"rounds_to_{tol:g}={r2t};dcelm_stalled_rounds=0;"
        f"fusion_stalled_rounds={stall}(all-reduce blocked while any "
        f"chip is down)",
    ))
    return rows, {}


def bench_gossip_topologies():
    """Consensus cost across ICI-realizable topologies at equal rounds.

    Small C so the graph term (not the ridge stiffness) dominates the
    essential spectral radius — isolates the topology effect.
    """
    rows = []
    V, Ni, L, M, C = 16, 48, 12, 1, 0.05
    ks = jax.random.split(jax.random.key(5), 2)
    H = jax.random.normal(ks[0], (V, Ni, L))
    T = jax.random.normal(ks[1], (V, Ni, M))
    state, P_, Q_ = dc_elm.simulate_init(H, T, C)
    beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
    rounds = 1500
    for kind in ["ring", "torus", "hypercube", "complete"]:
        g = consensus.build(kind, V)
        eng = engine.simulated_dc_elm(g, C)
        betas, _ = eng.run(
            state.betas, state.omegas, g.default_gamma(), rounds
        )
        dist = float(dc_elm.distance_to(betas, beta_star))
        bytes_round = g.d_max * L * M * 4
        rows.append((
            f"topology/{kind}16", 0.0,
            f"rounds={rounds};dist={dist:.5f};"
            f"lambda2={g.algebraic_connectivity:.3f};"
            f"dmax={g.d_max:.0f};bytes_per_node_per_round={bytes_round:.0f}",
        ))
    return rows, {}


def bench_compression_pareto(rounds: int = 2000, tol: float = 1e-2):
    """Accuracy-vs-bytes Pareto for compressed gossip (DESIGN.md §9).

    Every scheme runs the same `rounds` window on the same problem and
    reports rounds-to-tolerance, exact bytes-on-wire up to that round,
    and total window bytes — so the table answers both "what does it
    cost to *reach* the fp32 residual" and "what does it cost to reach
    and then *hold* it" (a serving window; this is where event-
    triggered rounds go quiet and win). The acceptance rows check that
    int8 + error feedback reaches the fp32 run's tolerance residual
    within 10x the fp32 rounds at <= 25% of the fp32 window bytes, on
    both mixers, including composed with a certified FaultModel trace.

    topk ships k=10% of entries and needs a reduced consensus gain
    (gamma x0.3) to contract — the classic CHOCO delta-compression
    trade.
    """
    from repro.core.compression import CompressionSpec

    rows = []
    V, Ni, L, M, C = 8, 32, 32, 4, 0.5
    ks = jax.random.split(jax.random.key(11), 2)
    H = (jax.random.normal(ks[0], (V, Ni, L)) / np.sqrt(L)).astype(
        jnp.float32
    )
    T = jax.random.normal(ks[1], (V, Ni, M)).astype(jnp.float32)
    state, P_, Q_ = dc_elm.simulate_init(H, T, C)
    beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
    g = consensus.build("hypercube", V)
    gamma = g.default_gamma()
    trace_fn = lambda b: dc_elm.distance_to(b, beta_star)  # noqa: E731
    fm = consensus.FaultModel.sample_certified(
        g, 0.2, num_rounds=64, window=16
    )
    keep = fm.edge_keep(64)

    schemes = [
        ("fp32", None, 1.0),
        ("bf16+ef", CompressionSpec(mode="bf16"), 1.0),
        ("int8+ef", CompressionSpec(mode="int8", tile=128), 1.0),
        ("int8-noef", CompressionSpec(mode="int8", tile=128,
                                      error_feedback=False), 1.0),
        ("topk10+ef", CompressionSpec(mode="topk", k=0.1), 0.3),
        ("int8+ef+event", CompressionSpec(mode="int8", tile=128,
                                          event_threshold=1e-3), 1.0),
    ]

    def measure(eng, gscale):
        betas, tr = eng.run(
            state.betas, state.omegas, gamma * gscale, rounds,
            trace_fn=trace_fn,
        )
        tr = np.asarray(tr)
        hit = np.nonzero(tr < tol)[0]
        r2t = int(hit[0]) + 1 if hit.size else -1
        ws = eng.wire_stats
        b2t = float(ws.per_round_bytes[:r2t].sum()) if r2t > 0 else -1.0
        return r2t, b2t, float(ws.bytes_on_wire), float(tr[-1]), ws

    base = {}
    for faulted in (False, True):
        tag = "faulty/" if faulted else "dense/"
        for name, spec, gscale in schemes:
            eng = engine.simulated_dc_elm(g, C, compress=spec)
            if faulted:
                eng = engine.with_faults(eng, keep)
            r2t, b2t, bwin, final, ws = measure(eng, gscale)
            key = tag + name
            base[key] = (r2t, b2t, bwin)
            fp = base[tag + "fp32"]
            rows.append((
                f"compression/{key}", 0.0,
                f"rounds_to_{tol:g}={r2t};bytes_to_tol={b2t:.0f};"
                f"window_bytes={bwin:.0f};window_ratio={bwin/fp[2]:.3f};"
                f"final_residual={final:.2e};"
                f"skip_frac={ws.links_skipped/max(ws.links_live,1):.2f}",
            ))
        # acceptance: int8+EF (event-triggered) vs the fp32 window
        fp, ev = base[tag + "fp32"], base[tag + "int8+ef+event"]
        ok_rounds = 0 < ev[0] <= 10 * max(fp[0], 1)
        ok_bytes = ev[2] <= 0.25 * fp[2]
        rows.append((
            f"compression/{tag}acceptance", 0.0,
            f"int8_ef_within_10x_rounds={ok_rounds};"
            f"bytes_le_25pct_fp32={ok_bytes};"
            f"rounds={ev[0]}v{fp[0]};bytes_ratio={ev[2]/fp[2]:.3f}",
        ))

    # the same comparison on the ppermute production path (+ faults),
    # in a subprocess with 8 fake host devices; residuals are sampled
    # between cached shard_map(scan) blocks (period-aligned with the
    # fault trace) since per-round traces are a dense-path feature
    import subprocess
    import sys

    code = f"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import consensus, dc_elm, engine, gossip
from repro.core.compression import CompressionSpec
from repro.utils import compat
V, Ni, L, M, C = {V}, {Ni}, {L}, {M}, {C}
# block == the fault-trace period: a bare FaultyMixer restarts its
# round counter per run() call, so period-aligned blocks keep the fp32
# baseline on the same certified cyclic trace the compressed schemes
# (which carry an absolute round counter) replay
rounds, tol, block = {rounds}, {tol}, 64
mesh = compat.make_mesh((8,), ('data',))
ks = jax.random.split(jax.random.key(11), 2)
H = (jax.random.normal(ks[0], (V, Ni, L)) / np.sqrt(L)).astype(jnp.float32)
T = jax.random.normal(ks[1], (V, Ni, M)).astype(jnp.float32)
state, P_, Q_ = dc_elm.simulate_init(H, T, C)
beta_star = dc_elm.centralized_from_node_stats(P_, Q_, C)
spec = gossip.GossipSpec(axes=('data',), kinds=('hypercube',))
g = spec.to_graph({{'data': V}})
gamma = g.default_gamma()
fm = consensus.FaultModel.sample_certified(g, 0.2, num_rounds=64, window=16)
keep = fm.edge_keep(64)
for faulted in (False, True):
    tag = 'ppermute_faulty/' if faulted else 'ppermute/'
    base = {{}}
    for name, cs in [('fp32', None),
                     ('int8+ef', CompressionSpec(mode='int8', tile=128)),
                     ('int8+ef+event', CompressionSpec(
                          mode='int8', tile=128, event_threshold=1e-3))]:
        eng = engine.sharded_dc_elm(mesh, spec, C, compress=cs)
        if faulted:
            eng = engine.with_faults(eng, keep)
        betas, stats, r2t, prb = state.betas, None, -1, []
        for b in range(rounds // block):
            betas, _ = eng.run(betas, state.omegas, gamma, block)
            ws = eng.wire_stats
            stats = ws if stats is None else stats + ws
            prb.append(ws.per_round_bytes)
            if r2t < 0 and float(dc_elm.distance_to(betas, beta_star)) < tol:
                r2t = (b + 1) * block
        prb = np.concatenate(prb)
        b2t = float(prb[:r2t].sum()) if r2t > 0 else -1.0
        base[name] = (r2t, stats.bytes_on_wire)
        print(f"ROW,compression/{{tag}}{{name}},0.0,"
              f"rounds_to_tol_le={{r2t}};bytes_to_tol={{b2t:.0f}};"
              f"window_bytes={{stats.bytes_on_wire}};"
              f"window_ratio={{stats.bytes_on_wire/base[list(base)[0]][1]:.3f}};"
              f"final_residual={{float(dc_elm.distance_to(betas, beta_star)):.2e}};"
              f"skip_frac={{stats.links_skipped/max(stats.links_live,1):.2f}}")
    fp, ev = base['fp32'], base['int8+ef+event']
    ok_rounds = 0 < ev[0] <= 10 * max(fp[0], 1)
    ok_bytes = ev[1] <= 0.25 * fp[1]
    print(f"ROW,compression/{{tag}}acceptance,0.0,"
          f"int8_ef_within_10x_rounds={{ok_rounds}};"
          f"bytes_le_25pct_fp32={{ok_bytes}};"
          f"rounds={{ev[0]}}v{{fp[0]}};bytes_ratio={{ev[1]/fp[1]:.3f}}")
print('DONE')
"""
    env = dict(os.environ)
    # the child's 8 fake host devices are CPU devices; on a chip host it
    # must not reach for the TPU this parent already holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    ) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=1800,
    )
    if "DONE" not in r.stdout:
        rows.append((
            "compression/ppermute", 0.0,
            f"ERROR:{r.stderr.strip().splitlines()[-1] if r.stderr else 'unknown'}",
        ))
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",", 3)
            rows.append((name, float(us), derived))
    return rows, {}


def bench_consensus_profile():
    """Gossip-round arm choice and utilization vs the roofline model.

    For each (graph, V, L) point: the measured wall time of the arm the
    dispatcher actually picks (``elm_gossip_ops.prefers_dense``) next
    to the dense round, and the ``analysis/roofline.py``
    ``gossip_round_terms`` modeled times for both arms — the model that
    drives the autotuner's candidate pruning and the dense-fallback
    heuristic, shown against ground truth so drift is visible.
    """
    import functools

    from repro.analysis.roofline import gossip_round_terms
    from repro.core.consensus import build
    from repro.kernels import elm_gossip_ops
    from repro.kernels.elm_gossip_ref import (
        dense_gossip_rounds,
        neighbor_lists,
    )

    rows = []
    R, M = 8, 8
    for kind, V, L in [
        ("hypercube", 256, 128), ("hypercube", 1024, 128),
        ("complete", 256, 128),
    ]:
        g = build(kind, V)
        d_max = int(round(g.d_max))
        ks = jax.random.split(jax.random.key(0), 2)
        betas = jax.random.normal(ks[0], (V, L, M), jnp.float32)
        omegas = jax.random.normal(ks[1], (V, L, L), jnp.float32) / L
        adj = jnp.asarray(g.adjacency, jnp.float32)[None]
        degd = jnp.sum(adj, axis=-1)
        idx, w, deg = neighbor_lists(adj)
        scale = jnp.float32(0.9 / d_max / (V * 10.0))
        dense = jax.jit(
            functools.partial(dense_gossip_rounds, num_rounds=R)
        )
        dense_us = _timeit_us(dense, betas, omegas, adj, degd, scale)
        to_dense = elm_gossip_ops.prefers_dense(V, d_max, L, M)
        if to_dense:
            fused_us = dense_us
        else:
            fused_us = _timeit_us(
                lambda b: elm_gossip_ops.fused_gossip_rounds(
                    b, omegas, idx, w, deg, scale, num_rounds=R,
                ),
                betas,
            )
        mn = gossip_round_terms(V, d_max, L, M)
        md = gossip_round_terms(V, d_max, L, M, dense=True)
        rows.append((
            f"consensus/{kind}_V{V}_L{L}", fused_us / R,
            f"arm={'dense' if to_dense else 'neighbor'};"
            f"dense_us_per_round={dense_us / R:.0f};"
            f"measured_ratio={dense_us / fused_us:.2f};"
            f"modeled_compute_ratio="
            f"{md['t_compute'] / mn['t_compute']:.2f};"
            f"modeled_round_us={mn['t_round'] * 1e6:.1f}",
        ))
    return rows, {}


PROFILES = {
    "gram": bench_gram,
    "stats": bench_stats_profile,
    "consensus": bench_consensus_profile,
    "ssd": bench_ssd,
    "attn": bench_attention,
    "online": bench_online_vs_direct,
    "comm": bench_consensus_vs_incremental,
    "topology": bench_gossip_topologies,
    "streaming": bench_streaming_driver,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="single-profile microbench")
    ap.add_argument(
        "--profile", default="stats", choices=sorted(PROFILES),
        help="which microbench rows to print (default: stats)",
    )
    args = ap.parse_args(argv)
    rows, _ = PROFILES[args.profile]()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
