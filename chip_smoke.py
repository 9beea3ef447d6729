#!/usr/bin/env python3
"""Chip smoke test: the DC-ELM learn -> gossip -> serve path on a TPU.

    python chip_smoke.py               # one chip: learn+serve, gossip, tenants
    python chip_smoke.py --chips 4     # four chips: the sharded ppermute path
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny [--chips 4]
                                       # CPU rehearsal, interpret-mode kernels

Everything runs in this one process through the library's public entry
points (``engine.simulated_dc_elm`` / ``sharded_dc_elm``,
``stream_init`` / ``stream_chunk``, ``BetaStore`` / ``TenantRegistry``,
``ELMServer``), at deployment widths, on data made from ``--seed``.
Each phase prints one line: which arm ran (a Pallas kernel shows up as
``tpu_custom_call`` in the lowered program), its errors against an
independent reference, its compile time, and whether it passed. Any
mismatch, exception or missing Pallas arm exits non-zero. Without a TPU
the script exits non-zero before printing a result (``--tiny`` is the
explicit exception). The last line of a passing run is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, and
otherwise in ``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: tolerances (relative max-abs errors unless named otherwise)
TOL_MOMENTS = 1e-4  # (P, Q) and Omega vs float64 NumPy
TOL_DIST = 1e-2  # dc_elm.distance_to(betas, beta*) after the rounds
TOL_SERVE = 1e-4  # served rows vs NumPy g(XW+b) beta in float64
TOL_ARMS = 1e-4  # neighbor vs dense arm; sharded vs one-chip dense

FULL = {
    "learn": dict(
        V=64, Ni=4096, D=784, L=1024, M=10, dN=256, rounds=300,
        requests=(1, 3, 16, 17, 64, 200, 700, 1500),
    ),
    "gossip": dict(dim=10, Ni=256, L=128, M=8, rounds=200),
    "tenants": dict(T=64, rows=1024),
    "sharded": dict(Ni=32768, D=784, L=2048, M=10, rounds=300),
}
TINY = {
    "learn": dict(
        V=8, Ni=256, D=64, L=256, M=3, dN=16, rounds=200,
        requests=(1, 3, 16, 17, 40, 100),
    ),
    # small L: the CPU's dense-round slack still picks the neighbor arm
    "gossip": dict(dim=8, Ni=32, L=24, M=2, rounds=10),
    "tenants": dict(T=8, rows=96),
    "sharded": dict(Ni=256, D=64, L=256, M=3, rounds=200),
}


sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import consensus, dc_elm, engine, gossip, stats  # noqa: E402
from repro.core.features import make_random_features  # noqa: E402
from repro.kernels import elm_predict_ops  # noqa: E402
from repro.serving import BetaStore, ELMServer, TenantRegistry  # noqa: E402
from repro.utils import compat  # noqa: E402


def log(**kw):
    print(json.dumps(kw, default=float), flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def arm_of(fn, *args) -> str:
    """Which arm a jitted public entry point lowers to."""
    text = jax.jit(fn).lower(*args).as_text()
    return "pallas" if "tpu_custom_call" in text else "xla"


def want_pallas(arm: str) -> bool:
    # the CPU rehearsal runs the kernels in interpret mode (plain HLO)
    return arm == "pallas" or jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Data (made on the device from --seed) and references
# ---------------------------------------------------------------------------


def make_data(key, lead, D, M):
    """Pixel-like inputs in [0, 1) and one-hot labels of a random
    linear teacher: an MNIST-shaped classification task."""
    kx, kt = jax.random.split(key)
    X = jax.random.uniform(kx, (*lead, D), jnp.float32)
    teacher = jax.random.normal(kt, (D, M), jnp.float32)
    labels = jnp.argmax(
        jnp.dot(X - 0.5, teacher, precision="highest"), axis=-1
    )
    return X, jax.nn.one_hot(labels, M, dtype=jnp.float32)


def features_ref(fmap, X):
    """Independent hidden layer: plain XLA at HIGHEST precision."""
    return jax.nn.sigmoid(
        jnp.dot(X, fmap.weights, precision="highest") + fmap.bias
    )


@jax.jit
def _moments_ref(H, T):
    dims = (((0,), (0,)), ((), ()))
    P_ = jax.lax.dot_general(H, H, dims, precision="highest")
    Q_ = jax.lax.dot_general(H, T, dims, precision="highest")
    return P_, Q_


def beta_star(P_, Q_, C):
    """Centralized ridge solution, solved in float64 on the host."""
    P64 = np.asarray(P_, np.float64)
    A = P64 + np.eye(P64.shape[0]) / C
    return np.linalg.solve(A, np.asarray(Q_, np.float64))


def numpy_predict(fmap, x, beta):
    W = np.asarray(fmap.weights, np.float64)
    b = np.asarray(fmap.bias, np.float64)
    z = np.asarray(x, np.float64) @ W + b
    return (1.0 / (1.0 + np.exp(-z))) @ np.asarray(beta, np.float64)


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_learn_serve(cfg, key, seed):
    """stream_init (fused stats + Cholesky Omega) on a random geometric
    network, two streamed chunks published to a BetaStore, then an
    ELMServer answering requests of mixed sizes."""
    V, Ni, D, L, M, dN, R, sizes = (
        cfg[k] for k in ("V", "Ni", "D", "L", "M", "dN", "rounds", "requests")
    )
    k_data, k_fmap, k_test = jax.random.split(key, 3)
    graph = consensus.random_geometric(V, 0.25, seed=seed)
    X, T = make_data(k_data, (V, Ni + 2 * dN), D, M)
    fmap = make_random_features(k_fmap, D, L, scale=0.1)
    C = 1.0 / (V * Ni)  # ridge ~ the per-row data scale: fast consensus
    eng = engine.simulated_dc_elm(graph, C)
    gamma = graph.default_gamma()
    X0, T0 = X[:, :Ni], T[:, :Ni]

    init = jax.jit(
        lambda x, t: eng.stream_init(X_nodes=x, T_nodes=t, feature_map=fmap)
    )
    lowered = init.lower(X0, T0)
    arm = "pallas" if "tpu_custom_call" in lowered.as_text() else "xla"
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    state, init_s = timed(compiled, X0, T0)

    # (P, Q) of the stats entry point vs float64 NumPy, first/last node;
    # Omega vs the inverse of the float64 ridge Gram
    moments_err, omega_err = 0.0, 0.0
    W64 = np.asarray(fmap.weights, np.float64)
    b64 = np.asarray(fmap.bias, np.float64)
    for v in (0, V - 1):
        P_, Q_ = stats.raw_moments(X0[v], T0[v], fmap)
        x64 = np.asarray(X0[v], np.float64)
        H64 = 1.0 / (1.0 + np.exp(-(x64 @ W64 + b64)))
        P64 = H64.T @ H64
        Q64 = H64.T @ np.asarray(T0[v], np.float64)
        moments_err = max(moments_err, rel_err(P_, P64), rel_err(Q_, Q64))
        gram = P64 + np.eye(L) / (V * C)
        resid = np.asarray(state.omegas[v], np.float64) @ gram - np.eye(L)
        omega_err = max(omega_err, float(np.max(np.abs(resid))))

    # reference moments over everything seen so far, for beta*
    Pc, Qc = _moments_ref(
        features_ref(fmap, X0.reshape(-1, D)), T0.reshape(-1, M)
    )
    store = BetaStore()
    dists, chunk_s = [], []
    for c in range(2):
        rows = slice(Ni + c * dN, Ni + (c + 1) * dN)
        dH, dT = fmap(X[:, rows]), T[:, rows]
        dP, dQ = _moments_ref(dH.reshape(-1, L), dT.reshape(-1, M))
        Pc, Qc = Pc + dP, Qc + dQ
        target = jnp.asarray(beta_star(Pc, Qc, C), jnp.float32)
        t0 = time.perf_counter()
        state, trace = eng.stream_chunk(
            state, added=(dH, dT), gamma=gamma, num_iters=R,
            trace_fn=lambda b: dc_elm.distance_to(b, target),
            publish_to=store,
        )
        trace = np.asarray(trace)
        chunk_s.append(time.perf_counter() - t0)
        dists.append((float(trace[0]), float(trace[-1])))
    falling = all(end < start for start, end in dists)
    final_dist = dists[-1][1]

    # serve: mixed request sizes, one over the largest bucket (split)
    Xq = np.asarray(jax.random.uniform(k_test, (sum(sizes), D)))
    server = ELMServer(fmap, store)
    offs = np.cumsum([0, *sizes])
    uids = [
        server.submit(Xq[offs[i]:offs[i + 1]]) for i in range(len(sizes))
    ]
    t0 = time.perf_counter()
    responses = {r.uid: r for r in server.flush()}
    serve_s = time.perf_counter() - t0
    snap = store.snapshot()
    serve_err = 0.0
    for i, uid in enumerate(uids):
        r = responses[uid]
        assert r.version == snap.version, (r.version, snap.version)
        want = numpy_predict(
            fmap, Xq[offs[i]:offs[i + 1]], snap.betas[r.node]
        )
        serve_err = max(serve_err, rel_err(r.y, want))
    serve_arm = arm_of(
        lambda x, b: elm_predict_ops.predict_map(x, fmap, b),
        jnp.asarray(Xq[:1024]), snap.betas[0],
    )
    ok = (
        want_pallas(arm) and want_pallas(serve_arm)
        and moments_err <= TOL_MOMENTS and omega_err <= TOL_MOMENTS
        and falling and final_dist <= TOL_DIST
        and serve_err <= TOL_SERVE and len(responses) == len(sizes)
    )
    log(
        phase="learn_serve", ok=ok, stats_arm=arm, serve_arm=serve_arm,
        V=V, Ni=Ni, D=D, L=L, M=M, d_max=graph.d_max,
        init_compile_s=compile_s, init_run_s=init_s,
        chunk_s=chunk_s, serve_flush_s=serve_s,
        moments_rel_err=moments_err, omega_resid=omega_err,
        dist_start_end=dists, serve_rel_err=serve_err,
        requests=len(sizes), batches=server.metrics["batches"],
    )
    return ok, (fmap, snap.betas)


def phase_gossip(cfg, key):
    """V = 2^dim hypercube: NeighborMixer (the Pallas gossip kernel)
    against DenseMixer on the same state for the same rounds."""
    dim, Ni, L, M, R = (cfg[k] for k in ("dim", "Ni", "L", "M", "rounds"))
    V = 1 << dim
    graph = consensus.hypercube(dim)
    kh, kt = jax.random.split(key)
    H = jax.random.uniform(kh, (V, Ni, L), jnp.float32)
    T = jax.random.normal(kt, (V, Ni, M), jnp.float32)
    C = 1.0 / (V * Ni)
    neighbor = engine.simulated_dc_elm(graph, C, mixer="neighbor")
    dense = engine.simulated_dc_elm(graph, C, mixer="dense")
    state = dense.stream_init(H, T)
    gamma = graph.default_gamma()

    def rounds(eng):
        return jax.jit(lambda b, o: eng.run(b, o, gamma, R)[0])

    arm = arm_of(rounds(neighbor), state.betas, state.omegas)
    t0 = time.perf_counter()
    run_n = rounds(neighbor).lower(state.betas, state.omegas).compile()
    compile_s = time.perf_counter() - t0
    out_n, run_s = timed(run_n, state.betas, state.omegas)
    out_d, dense_s = timed(rounds(dense), state.betas, state.omegas)
    err = rel_err(out_n, out_d)
    spread0 = float(dc_elm.consensus_error(state.betas))
    spread = float(dc_elm.consensus_error(out_n))
    ok = want_pallas(arm) and err <= TOL_ARMS and spread < spread0
    log(
        phase="gossip", ok=ok, neighbor_arm=arm, V=V, L=L, M=M,
        d_max=graph.d_max, rounds=R, compile_s=compile_s,
        neighbor_run_s=run_s, dense_first_call_s=dense_s,
        neighbor_vs_dense_rel_err=err,
        consensus_error_start_end=(spread0, spread),
    )
    return ok


def phase_tenants(cfg, key, fmap, betas):
    """One flush mixing every tenant through the stacked kernel."""
    T_, rows = cfg["T"], cfg["rows"]
    registry = TenantRegistry(
        {f"t{t}": betas[t % betas.shape[0]] for t in range(T_)}
    )
    server = ELMServer(fmap, registry, buckets=(rows,))
    sizes = np.full(T_, rows // T_)
    sizes[: rows - sizes.sum()] += 1
    D = fmap.in_dim
    Xq = np.asarray(jax.random.uniform(key, (rows, D)))
    offs = np.cumsum(np.concatenate([[0], sizes]))
    uids = [
        server.submit(Xq[offs[t]:offs[t + 1]], tenant=f"t{t}")
        for t in range(T_)
    ]
    t0 = time.perf_counter()
    responses = {r.uid: r for r in server.flush()}
    flush_s = time.perf_counter() - t0
    snap = registry.snapshot()
    err = 0.0
    for t, uid in enumerate(uids):
        want = numpy_predict(
            fmap, Xq[offs[t]:offs[t + 1]], snap.beta(f"t{t}")
        )
        err = max(err, rel_err(responses[uid].y, want))
    tids = jnp.asarray(np.repeat(np.arange(T_), sizes), jnp.int32)
    arm = arm_of(
        lambda x, b, i: elm_predict_ops.predict_stacked(x, fmap, b, i),
        jnp.asarray(Xq), snap.betas, tids,
    )
    ok = (
        want_pallas(arm) and err <= TOL_SERVE
        and server.metrics["batches"] == 1 and len(responses) == T_
    )
    log(
        phase="tenants", ok=ok, stacked_arm=arm, tenants=T_, rows=rows,
        launches=server.metrics["batches"], first_flush_s=flush_s,
        rel_err=err,
    )
    return ok


def phase_sharded(cfg, key):
    """One node per chip on a 4-device mesh (the ppermute production
    path) against the same four nodes run by the dense engine on one
    chip, and against beta*."""
    Ni, D, L, M, R = (cfg[k] for k in ("Ni", "D", "L", "M", "rounds"))
    devices = jax.devices()
    V = len(devices)
    mesh = compat.make_mesh((V,), ("data",))
    spec = gossip.GossipSpec(axes=("data",), kinds=("ring",))
    graph = spec.to_graph({"data": V})
    k_data, k_fmap = jax.random.split(key)
    on_mesh = NamedSharding(mesh, P("data"))
    X, T = jax.jit(
        lambda k: make_data(k, (V, Ni), D, M),
        out_shardings=(on_mesh, on_mesh),
    )(k_data)
    # each device must hold its own node's quarter of the data
    shards = {s.device: s.data.shape for s in X.addressable_shards}
    split_ok = len(shards) == V and all(
        shape == (1, Ni, D) for shape in shards.values()
    )
    fmap = make_random_features(k_fmap, D, L, scale=0.1)
    C = 1.0 / (V * Ni)
    gamma = graph.default_gamma()

    sharded = engine.sharded_dc_elm(mesh, spec, C)
    t0 = time.perf_counter()
    state = sharded.stream_init(X_nodes=X, T_nodes=T, feature_map=fmap)
    out, _ = sharded.stream_chunk(state, gamma=gamma, num_iters=R)
    out = jax.block_until_ready(out).betas
    sharded_s = time.perf_counter() - t0
    out_devices = len({s.device for s in out.addressable_shards})
    stats_arm = arm_of(
        lambda x, t: sharded.stream_init(
            X_nodes=x, T_nodes=t, feature_map=fmap
        ).omegas,
        X, T,
    )

    one = devices[0]
    X1, T1 = jax.device_put(X, one), jax.device_put(T, one)
    dense = engine.simulated_dc_elm(graph, C)
    t0 = time.perf_counter()
    ref_state = dense.stream_init(X_nodes=X1, T_nodes=T1, feature_map=fmap)
    ref, _ = dense.stream_chunk(ref_state, gamma=gamma, num_iters=R)
    ref = jax.block_until_ready(ref).betas
    dense_s = time.perf_counter() - t0

    Pc, Qc = _moments_ref(
        features_ref(fmap, X1.reshape(-1, D)), T1.reshape(-1, M)
    )
    target = jnp.asarray(beta_star(Pc, Qc, C), jnp.float32)
    err = rel_err(out, ref)
    dist = float(dc_elm.distance_to(jax.device_put(out, one), target))
    dist0 = float(dc_elm.distance_to(ref_state.betas, target))
    ok = (
        split_ok and out_devices == V and want_pallas(stats_arm)
        and err <= TOL_ARMS and dist < dist0 and dist <= TOL_DIST
    )
    log(
        phase="sharded", ok=ok, stats_arm=stats_arm, devices=V,
        input_shards={str(d): s for d, s in shards.items()},
        Ni=Ni, D=D, L=L, M=M, rounds=R,
        sharded_first_call_s=sharded_s, dense_one_chip_first_call_s=dense_s,
        sharded_vs_dense_rel_err=err, dist_start_end=(dist0, dist),
    )
    return ok


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--tiny", action="store_true",
        help="CPU rehearsal at toy sizes with interpret-mode kernels",
    )
    args = ap.parse_args()
    if args.tiny:
        # set before JAX's backend starts: the dispatchers then take
        # their Pallas branches in interpret mode, and a 4-chip
        # rehearsal gets 4 host devices in this same process
        os.environ["REPRO_FORCE_INTERPRET"] = "1"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.tiny:
        print(
            f"chip_smoke: no TPU found (platform {dev.platform!r}); "
            "use --tiny for the CPU rehearsal",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
            f"found {len(devices)}",
            file=sys.stderr,
        )
        return 2
    sizes = TINY if args.tiny else FULL
    key = jax.random.key(args.seed)
    k_learn, k_gossip, k_tenants, k_sharded = jax.random.split(key, 4)
    results = {}

    def run(name, fn, *a):
        t0 = time.perf_counter()
        try:
            results[name] = fn(*a)
        except Exception:  # a phase failure fails the run, never exit 0
            traceback.print_exc()
            log(phase=name, ok=False, error=traceback.format_exc(limit=3))
            results[name] = False
        print(f"# {name}: {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    if args.chips == 4:
        run("sharded", phase_sharded, sizes["sharded"], k_sharded)
    else:
        learned = {}

        def learn():
            ok, learned["out"] = phase_learn_serve(
                sizes["learn"], k_learn, args.seed
            )
            return ok

        run("learn_serve", learn)
        run("gossip", phase_gossip, sizes["gossip"], k_gossip)
        if "out" in learned:
            run(
                "tenants", phase_tenants, sizes["tenants"], k_tenants,
                *learned["out"],
            )
        else:
            results["tenants"] = False
    ok = all(bool(v) for v in results.values())
    print(json.dumps({
        "ok": ok,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices) if args.chips == 4 else 1,
        },
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
