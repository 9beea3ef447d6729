"""Measured kernel autotuner: roofline-pruned sweep, versioned cache.

The fused planes (kernels/elm_stats.py, kernels/elm_predict.py and
their lax.scan fallbacks) expose block knobs — ``block_n``/``block_l``
on the Pallas grid, ``chunk`` on the scan — whose optimum moves with
the problem point (N, D, L, M, dtype) and the backend. Hand-picked
values demonstrably lose away from the point they were picked at
(BENCH_stats.json once shipped a 0.54x row at N=8192). This module
makes the selection a *measured* decision:

1. **Candidate grid.** ``candidates`` enumerates power-of-two block
   sizes clamped to the problem dims, always including the current
   hard-coded defaults so a tuned cache can never be worse than the
   untuned code path on the machine that produced it.
2. **Roofline pruning.** ``roofline_prune`` scores each candidate with
   the same terms as ``analysis/roofline.py`` — a working-set test
   (does the candidate's resident set fit the VMEM/cache budget?) and
   a ``max(t_compute, t_memory)`` estimate built on the module's
   PEAK_FLOPS / HBM_BW constants (used for *relative* ranking; the
   constants cancel out of the comparison). Candidates whose working
   set blows the budget, or whose estimate is dominated (> PRUNE_FACTOR
   x the best in-budget estimate), are discarded before any
   measurement.
3. **Measurement.** Survivors are timed with the exact harness the
   plane benchmarks use (``benchmarks/_bench_util.py`` imports it from
   here): one warm-up call, then block_until_ready-bracketed repeats,
   *interleaved round-robin* across candidates so machine-speed drift
   (frequency scaling, noisy neighbours) hits every candidate equally
   instead of deciding the winner.
4. **Cache.** Winners persist to a schema-versioned JSON
   (``TUNED_kernels.json`` at the repo root by default, override with
   ``cache_path=`` or the ``REPRO_TUNED_CACHE`` env var), keyed by
   (op, impl, N, D, L, M, dtype, backend). Each entry records the
   winning config, its measured wall time, the jax version and the full
   measured sweep. An in-process LRU memo sits on top so the dispatch
   wrappers can consult the cache at trace time for free.

Lookup policy: exact point first, then the nearest-N entry for the
same (op, impl, D, L, M, dtype, backend) within a 4x ratio (serving
buckets hit the tuned table without tuning every batch shape), else
miss — and on a miss the dispatchers keep today's defaults, so
cold-start behavior is unchanged. A jax upgrade does not invalidate
entries outright (block optima are shape-driven, not version-driven);
instead ``tools/bench_gate.py`` re-measures nightly and *warns* when a
committed winner drifts >1.5x from fresh measurements.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import threading
import time
from collections import OrderedDict
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.analysis.roofline import HBM_BW, PEAK_FLOPS

SCHEMA_VERSION = 1
OPS = ("stats", "preact_stats", "predict", "stacked", "gossip")
IMPLS = ("scan", "pallas")

#: working-set budgets for the pruning test (bytes): VMEM for the
#: Pallas grid, an L2/L3-ish cache budget for the scan fallback
VMEM_BUDGET = 16 * 2**20
CACHE_BUDGET = 32 * 2**20
#: candidates whose roofline estimate exceeds the best in-budget
#: estimate by this factor are pruned without measurement
PRUNE_FACTOR = 1.5
#: measured walls within this factor of the fastest are considered a
#: tie; ties on the scan impl break toward the largest chunk
TIE_FACTOR = 1.03

#: the hard-coded defaults the dispatchers fall back to on a cache
#: miss (elm_stats_scan / elm_predict_scan / *_pallas signatures)
DEFAULTS = {
    ("stats", "scan"): {"chunk": 2048},
    ("preact_stats", "scan"): {"chunk": 2048},
    ("predict", "scan"): {"chunk": 4096},
    # stacked: the gathered (chunk, L, M) beta tiles dominate the
    # working set, so the default chunk sits below the single-beta scan
    ("stacked", "scan"): {"chunk": 2048},
    ("stats", "pallas"): {"block_n": 512, "block_l": 256},
    ("preact_stats", "pallas"): {"block_n": 512, "block_l": 256},
    ("predict", "pallas"): {"block_n": 512, "block_l": 256},
    ("stacked", "pallas"): {"block_n": 256, "block_l": 256},
    # gossip: the point maps V -> N and d_max -> D (kernels/elm_gossip);
    # scan "chunk" is neighbor slots per gather step, pallas "block_n"
    # is the node tile block_v
    ("gossip", "scan"): {"chunk": 8},
    ("gossip", "pallas"): {"block_n": 8},
}

_REPO_ROOT = Path(__file__).resolve().parents[3]


def default_cache_path() -> str:
    return os.environ.get(
        "REPRO_TUNED_CACHE", str(_REPO_ROOT / "TUNED_kernels.json")
    )


# ---------------------------------------------------------------------------
# Timing harness (shared with benchmarks/_bench_util.py)
# ---------------------------------------------------------------------------


def timeit_ms(fn, *args, repeats=3):
    """Min wall ms over `repeats` bracketed calls after one warm-up.

    The minimum, not the mean: scheduler preemptions and cache-state
    noise only ever make a call *slower*, so the min is the best
    estimate of the program's intrinsic cost — and the statistic least
    likely to flip a close fused-vs-unfused ratio between runs.
    """
    out = fn(*args)
    jax.block_until_ready(out)
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def paired_timeit_ms(fns, *args, repeats=3):
    """Interleaved min wall ms for several callables over shared args.

    The machine's speed can drift a lot on second timescales (CPU
    frequency scaling, noisy neighbours). Timing callables in separate
    back-to-back ``timeit_ms`` blocks bakes that drift into their
    *ratio* — enough to flip a close fused-vs-unfused comparison.
    Round-robin interleaving (repeat 1 of every fn, repeat 2 of every
    fn, ...) exposes all callables to the same machine episodes, so
    drift cancels out of the ratios and only the intrinsic cost
    difference survives the per-fn min.
    """
    for fn in fns:  # one warm-up each (compile + first-touch)
        jax.block_until_ready(fn(*args))
    best = [math.inf] * len(fns)
    for _ in range(repeats):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best[i] = min(best[i], time.perf_counter() - t0)
    return [b * 1e3 for b in best]


# ---------------------------------------------------------------------------
# Points, candidates, roofline pruning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TunePoint:
    """One (op, impl, problem, backend) tuning coordinate.

    ``T`` is the stacked-beta tenant count — the new block axis of the
    multi-tenant predict. It is required for op="stacked" and joins
    the cache key there (the beta block scales with T); the other ops
    keep T=0 and their keys are byte-identical to the pre-stacked
    schema, so committed caches stay valid.
    """

    op: str  # "stats" | "predict" | "stacked" | "gossip"
    impl: str  # "scan" | "pallas"
    N: int
    D: int
    L: int
    M: int
    dtype: str
    backend: str
    T: int = 0  # tenant count; stacked op only

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"op must be one of {OPS}, got {self.op!r}")
        if self.impl not in IMPLS:
            raise ValueError(
                f"impl must be one of {IMPLS}, got {self.impl!r}"
            )
        if self.op == "stacked" and self.T <= 0:
            raise ValueError(
                f"op='stacked' needs a tenant count T >= 1, got {self.T}"
            )

    @property
    def key(self) -> str:
        t = f"_T{self.T}" if self.T else ""
        return (
            f"{self.op}/{self.impl}/N{self.N}_D{self.D}_L{self.L}"
            f"_M{self.M}{t}_{self.dtype}/{self.backend}"
        )

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def flops(self) -> float:
        """Useful flops of the op (config-independent)."""
        N, D, L, M = self.N, self.D, self.L, self.M
        if self.op == "stats":
            return 2.0 * N * D * L + 2.0 * N * L * (L + M)
        if self.op == "preact_stats":
            # vertical mode: the feature matmul already happened across
            # column-sliced nodes; only bias+activation+moments remain
            return 2.0 * N * L * (L + M)
        if self.op == "gossip":
            # per round: neighbor-weighted gather-accumulate over D
            # slots plus the (L, L) @ (L, M) Omega contraction per node
            return 2.0 * N * D * L * M + 2.0 * N * L * L * M
        # predict and stacked share the useful-flop count: the stacked
        # gather adds traffic, not MACs
        return 2.0 * N * L * (D + M)


def candidates(point: TunePoint) -> list[dict]:
    """Power-of-two block grid clamped to the problem dims.

    Always contains the hard-coded default (clamped), so measuring the
    survivors can never produce a cache entry worse than the untuned
    path on the machine that measured it.
    """
    out = []
    if point.op == "gossip":
        if point.impl == "scan":
            # chunk = neighbor slots per gather step, capped at d_max
            chunks = {min(c, point.D) for c in (1, 2, 4, 8, 16, 32, 64)}
            chunks.add(min(DEFAULTS[("gossip", "scan")]["chunk"], point.D))
            return [{"chunk": c} for c in sorted(chunks)]
        bns = {min(b, point.N) for b in (8, 16, 32, 64)}
        bns.add(min(DEFAULTS[("gossip", "pallas")]["block_n"], point.N))
        return [{"block_n": b} for b in sorted(bns)]
    if point.impl == "scan":
        grid = (
            (256, 512, 1024, 2048, 4096)  # gathered tiles cap the chunk
            if point.op == "stacked"
            else (512, 1024, 2048, 4096, 8192, 16384)
        )
        chunks = {min(c, point.N) for c in grid}
        chunks.add(min(DEFAULTS[(point.op, "scan")]["chunk"], point.N))
        out = [{"chunk": c} for c in sorted(chunks)]
    else:
        bns = {min(b, point.N) for b in (128, 256, 512, 1024)}
        bls = {min(b, point.L) for b in (128, 256, 512)}
        d = DEFAULTS[(point.op, "pallas")]
        bns.add(min(d["block_n"], point.N))
        bls.add(min(d["block_l"], point.L))
        out = [
            {"block_n": bn, "block_l": bl}
            for bn in sorted(bns)
            for bl in sorted(bls)
        ]
    return out


def working_set_bytes(point: TunePoint, cfg: dict) -> float:
    """Resident bytes a candidate keeps hot (the VMEM/cache test)."""
    s = point.itemsize
    D, L, M, T = point.D, point.L, point.M, point.T
    if point.op == "gossip":
        N = point.N
        if point.impl == "scan":
            # state + f32 lap carry + the gathered (V, chunk, L*M) tile
            # (the chunk knob's term) + omegas + lists
            c = cfg["chunk"]
            return (
                s * N * L * M
                + 4.0 * N * L * M * (1 + c)
                + s * N * L * L
                + 8.0 * N * D
            )
        # pallas: full state resident + per-tile omega/lap/out blocks
        bn = cfg["block_n"]
        return (
            4.0 * N * L * M
            + 4.0 * bn * (L * L + 2 * L * M)
            + 8.0 * bn * D
        )
    if point.impl == "scan":
        c = cfg["chunk"]
        if point.op == "stats":
            # X/T chunk + W + H tile + f32 moment carries
            return s * (c * D + D * L + c * L + c * M) + 4.0 * (
                L * L + L * M
            )
        if point.op == "preact_stats":
            # Z chunk + H tile + T chunk + f32 moment carries
            return s * (2 * c * L + c * M) + 4.0 * (L * L + L * M)
        if point.op == "stacked":
            # X chunk + W + H tile + stacked betas + gathered per-row
            # beta tiles (the term that caps the chunk) + Y chunk
            return s * (c * D + D * L + c * L + c * M) + 4.0 * (
                T * L * M + c * L * M
            )
        # predict: X chunk + W + H tile + beta + Y chunk
        return s * (c * D + D * L + c * L + c * M) + 4.0 * L * M
    bn, bl = cfg["block_n"], cfg["block_l"]
    if point.op == "stats":
        # X tile + two W blocks + two H tiles + T tile + f32 P/Q blocks
        return s * (bn * D + 2 * D * bl + 2 * bn * bl + bn * M) + 4.0 * (
            bl * bl + bl * M
        )
    if point.op == "preact_stats":
        # two Z tiles + two H tiles + T tile + f32 P/Q blocks
        return s * (4 * bn * bl + bn * M) + 4.0 * (bl * bl + bl * M)
    if point.op == "stacked":
        # X tile + W block + H scratch + one tenant's (bl, M) beta tile
        # (double-buffered) + f32 out block
        return s * (bn * D + D * bl + bn * bl) + 4.0 * (
            2 * bl * M + bn * M
        )
    # predict: X tile + W block + H tile + beta block + f32 out block
    return s * (bn * D + D * bl + bn * bl + bl * M) + 4.0 * bn * M


def hbm_bytes(point: TunePoint, cfg: dict) -> float:
    """Modeled off-chip traffic for a candidate (roofline memory term).

    Captures the block-size tradeoff: small blocks re-touch the f32
    accumulators (scan) or re-stream X per (i, j) block pair (Pallas);
    large blocks spill the hidden tile out of the working-set budget.
    """
    s = point.itemsize
    N, D, L, M, T = point.N, point.D, point.L, point.M, point.T
    if point.op == "gossip":
        # per round: state read+write, omegas, neighbor lists; the scan
        # materializes the gathered (V, chunk, L*M) tiles — an extra
        # round trip when a tile spills the cache budget
        base = 4.0 * (2.0 * N * L * M + N * L * L) + 8.0 * N * D
        if point.impl == "scan":
            c = cfg["chunk"]
            base += 4.0 * N * D * L * M
            if 4.0 * N * c * L * M > CACHE_BUDGET / 2:
                base += 4.0 * N * D * L * M
        return base
    if point.impl == "scan":
        c = cfg["chunk"]
        steps = math.ceil(N / c)
        if point.op == "preact_stats":
            base = s * (N * L + N * M)  # Z and T stream through once
        else:
            base = s * (N * D + N * M)  # X and T stream through once
        carry = 2.0 * 4 * (L * L + L * M) * steps  # P/Q read+write per step
        # the hidden tile spills past the cache budget -> extra round trip
        spill = s * N * L if s * c * L > CACHE_BUDGET / 2 else 0.0
        out = (
            4.0 * (L * L + L * M)
            if point.op in ("stats", "preact_stats")
            else s * N * M
        )
        if point.op == "stacked":
            # the gathered (c, L, M) beta tiles are materialized per
            # step: N*L*M of gather traffic across the whole run
            base += 4.0 * N * L * M
        return base + carry + spill + out
    bn, bl = cfg["block_n"], cfg["block_l"]
    jblocks = math.ceil(L / bl)
    if point.op == "stats":
        # X re-streams once per upper-triangle (i, j) block pair
        xpasses = jblocks * (jblocks + 1) / 2
        return (
            s * N * D * xpasses
            + s * D * L * jblocks * math.ceil(N / bn)
            + 4.0 * (L * L + L * M)
        )
    if point.op == "preact_stats":
        # two (bn, bl) Z tiles per upper-triangle (i, j) block pair
        zpasses = jblocks * (jblocks + 1) / 2
        return s * 2.0 * N * bl * zpasses + 4.0 * (L * L + L * M)
    # predict/stacked: X re-streams once per j (L) block; the stacked
    # path additionally reads each row block's distinct tenants' betas
    # (at most min(T, bn) of them)
    base = s * N * D * jblocks + s * D * L * math.ceil(N / bn) + s * N * M
    if point.op == "stacked":
        base += 4.0 * min(T, bn) * L * M * math.ceil(N / bn)
    return base


def estimate(point: TunePoint, cfg: dict) -> dict:
    """Roofline terms for one candidate (relative ranking only)."""
    t_compute = point.flops / PEAK_FLOPS
    t_memory = hbm_bytes(point, cfg) / HBM_BW
    return {
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_estimate": max(t_compute, t_memory),
        "working_set": working_set_bytes(point, cfg),
    }


def roofline_prune(
    point: TunePoint, cands: list[dict], *, factor: float = PRUNE_FACTOR
) -> tuple[list[dict], list[dict]]:
    """(kept, pruned): drop candidates whose working set blows the
    VMEM/cache budget or whose roofline estimate is dominated."""
    budget = VMEM_BUDGET if point.impl == "pallas" else CACHE_BUDGET
    scored = [(estimate(point, c), c) for c in cands]
    in_budget = [sc for sc in scored if sc[0]["working_set"] <= budget]
    if not in_budget:  # degenerate point: keep the smallest working set
        in_budget = [min(scored, key=lambda sc: sc[0]["working_set"])]
    best = min(sc[0]["t_estimate"] for sc in in_budget)
    kept, pruned = [], []
    for est, c in in_budget:
        (kept if est["t_estimate"] <= factor * best else pruned).append(c)
    pruned.extend(c for est, c in scored if (est, c) not in in_budget)
    return kept, pruned


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def _problem(point: TunePoint):
    """The measurement arrays — same construction as the benches."""
    dt = jnp.dtype(point.dtype)
    ks = jax.random.split(jax.random.key(0), 4)
    if point.op == "gossip":
        # V <- N nodes, d_max <- D neighbor slots; a synthetic regular
        # graph (random indices, unit weights) matches the gather cost
        V, d = point.N, point.D
        betas = jax.random.normal(ks[0], (V, point.L, point.M)).astype(dt)
        omegas = jax.random.normal(
            ks[1], (V, point.L, point.L)
        ).astype(dt)
        idx = jax.random.randint(ks[2], (1, V, d), 0, V, dtype=jnp.int32)
        w = jnp.ones((1, V, d), dt)
        deg = jnp.full((1, V), float(d), dt)
        return betas, omegas, idx, w, deg, 0.01
    if point.op == "preact_stats":
        Z = jax.random.normal(ks[0], (point.N, point.L)).astype(dt)
        b = jax.random.normal(ks[2], (point.L,)).astype(jnp.float32)
        T = jax.random.normal(ks[3], (point.N, point.M)).astype(dt)
        return Z, b, T
    X = jax.random.normal(ks[0], (point.N, point.D)).astype(dt)
    W = jax.random.normal(ks[1], (point.D, point.L)).astype(dt)
    b = jax.random.normal(ks[2], (point.L,)).astype(jnp.float32)
    if point.op == "stats":
        T = jax.random.normal(ks[3], (point.N, point.M)).astype(dt)
        return X, W, b, T
    beta = jax.random.normal(
        ks[3], (point.L, point.M), dtype=jnp.float32
    )
    if point.op == "stacked":
        betas = jax.random.normal(
            ks[3], (point.T, point.L, point.M), dtype=jnp.float32
        )
        tids = jax.random.randint(
            jax.random.key(1), (point.N,), 0, point.T, dtype=jnp.int32
        )
        return X, W, b, betas, tids
    return X, W, b, beta


def candidate_fn(point: TunePoint, cfg: dict):
    """A jitted callable running the point's op with one candidate."""
    if point.op == "gossip":
        # a short fixed round count: enough for the per-round cost to
        # dominate the scan setup, cheap enough to sweep
        if point.impl == "scan":
            from repro.kernels.elm_gossip_ref import elm_gossip_scan

            return jax.jit(
                functools.partial(
                    elm_gossip_scan, num_rounds=4, chunk=cfg["chunk"]
                )
            )
        from repro.kernels.elm_gossip import elm_gossip_pallas

        return jax.jit(
            functools.partial(
                elm_gossip_pallas, num_rounds=4,
                block_v=cfg["block_n"],
                interpret=jax.default_backend() != "tpu",
            )
        )
    if point.impl == "scan":
        if point.op == "stats":
            from repro.kernels.elm_stats_ref import elm_stats_scan

            return jax.jit(
                functools.partial(
                    elm_stats_scan, activation="sigmoid",
                    chunk=cfg["chunk"],
                )
            )
        if point.op == "preact_stats":
            from repro.kernels.elm_stats_ref import preact_stats_scan

            return jax.jit(
                functools.partial(
                    preact_stats_scan, activation="sigmoid",
                    chunk=cfg["chunk"],
                )
            )
        if point.op == "stacked":
            from repro.kernels.elm_predict_ref import (
                elm_predict_stacked_scan,
            )

            return jax.jit(
                functools.partial(
                    elm_predict_stacked_scan, activation="sigmoid",
                    chunk=cfg["chunk"],
                )
            )
        from repro.kernels.elm_predict_ref import elm_predict_scan

        return jax.jit(
            functools.partial(
                elm_predict_scan, activation="sigmoid", chunk=cfg["chunk"]
            )
        )
    if point.op == "stats":
        from repro.kernels.elm_stats import elm_stats_pallas

        return jax.jit(
            functools.partial(
                elm_stats_pallas, activation="sigmoid", **cfg
            )
        )
    if point.op == "preact_stats":
        from repro.kernels.elm_stats import elm_preact_stats_pallas

        return jax.jit(
            functools.partial(
                elm_preact_stats_pallas, activation="sigmoid", **cfg
            )
        )
    if point.op == "stacked":
        from repro.kernels.elm_predict import elm_predict_stacked_pallas

        return jax.jit(
            functools.partial(
                elm_predict_stacked_pallas, activation="sigmoid", **cfg
            )
        )
    from repro.kernels.elm_predict import elm_predict_pallas

    return jax.jit(
        functools.partial(elm_predict_pallas, activation="sigmoid", **cfg)
    )


def measure_candidates(
    point: TunePoint, cands: list[dict], *, repeats: int = 2
) -> list[dict]:
    """Time each candidate on the point's problem; sorted fastest first.

    Candidates are measured round-robin (``paired_timeit_ms``) so the
    winner reflects intrinsic cost, not which candidate happened to run
    during a fast spell of a drifting machine.
    """
    args = _problem(point)
    fns = [candidate_fn(point, cfg) for cfg in cands]
    walls = paired_timeit_ms(fns, *args, repeats=repeats)
    results = [
        {"config": cfg, "wall_ms": ms} for cfg, ms in zip(cands, walls)
    ]
    return sorted(results, key=lambda r: r["wall_ms"])


# ---------------------------------------------------------------------------
# Cache (JSON file + in-process LRU memo)
# ---------------------------------------------------------------------------

_MEMO_SIZE = 256
_memo: OrderedDict = OrderedDict()
_json_cache: dict = {}  # path -> (mtime, payload)
_lock = threading.Lock()


def clear_memo() -> None:
    """Drop the in-process lookup memo (tests; after cache edits)."""
    with _lock:
        _memo.clear()
        _json_cache.clear()


def load_cache(cache_path: str | None = None) -> dict:
    """The parsed cache payload ({"schema": .., "entries": {..}})."""
    path = cache_path or default_cache_path()
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {"schema": SCHEMA_VERSION, "entries": {}}
    with _lock:
        hit = _json_cache.get(path)
        if hit is not None and hit[0] == mtime:
            return hit[1]
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except (OSError, json.JSONDecodeError):
        return {"schema": SCHEMA_VERSION, "entries": {}}
    if payload.get("schema") != SCHEMA_VERSION:
        # unknown future schema: behave as a miss everywhere rather
        # than misapply configs recorded under different semantics
        payload = {"schema": SCHEMA_VERSION, "entries": {}}
    with _lock:
        _json_cache[path] = (mtime, payload)
    return payload


def _save_cache(payload: dict, cache_path: str) -> None:
    tmp = cache_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, cache_path)
    clear_memo()


def _resolve_point(op, N, D, L, M, dtype, backend, impl, T=0) -> TunePoint:
    backend = backend or jax.default_backend()
    impl = impl or ("pallas" if backend == "tpu" else "scan")
    return TunePoint(
        op=op, impl=impl, N=int(N), D=int(D), L=int(L), M=int(M),
        dtype=str(jnp.dtype(dtype)), backend=backend, T=int(T),
    )


def lookup(
    op: str, N: int, D: int, L: int, M: int, dtype, *,
    backend: str | None = None, impl: str | None = None,
    cache_path: str | None = None, T: int = 0,
) -> dict | None:
    """The tuned config for a point, or None on a cache miss.

    Exact key first, then the nearest-N entry for the same
    (op, impl, D, L, M, [T,] dtype, backend) within a 4x N ratio.
    Memoized in-process (LRU of {_MEMO_SIZE}) so trace-time
    consultation from the dispatch wrappers is effectively free.
    """
    point = _resolve_point(op, N, D, L, M, dtype, backend, impl, T)
    path = cache_path or default_cache_path()
    memo_key = (path, point.key)
    with _lock:
        if memo_key in _memo:
            _memo.move_to_end(memo_key)
            return _memo[memo_key]
    entries = load_cache(path)["entries"]
    cfg = None
    hit = entries.get(point.key)
    if hit is not None:
        cfg = dict(hit["config"])
    else:
        t = f"_T{point.T}" if point.T else ""
        suffix = (
            f"_D{point.D}_L{point.L}_M{point.M}{t}_{point.dtype}"
            f"/{point.backend}"
        )
        prefix = f"{point.op}/{point.impl}/N"
        best_ratio = 4.0
        for key, entry in entries.items():
            if not (key.startswith(prefix) and key.endswith(suffix)):
                continue
            n = int(key[len(prefix):].split("_", 1)[0])
            ratio = max(n, point.N) / max(1, min(n, point.N))
            if ratio <= best_ratio:
                best_ratio = ratio
                cfg = dict(entry["config"])
    with _lock:
        _memo[memo_key] = cfg
        _memo.move_to_end(memo_key)
        while len(_memo) > _MEMO_SIZE:
            _memo.popitem(last=False)
    return cfg


def tune(
    op: str, N: int, D: int, L: int, M: int, dtype, *,
    backend: str | None = None, impl: str | None = None,
    repeats: int = 2, cache_path: str | None = None,
    force: bool = False, prune_factor: float = PRUNE_FACTOR,
    T: int = 0,
) -> dict:
    """Sweep-and-cache one point; returns the winning config.

    Generates the candidate grid, roofline-prunes it, measures the
    survivors and persists the winner. Scan candidates within
    ``TIE_FACTOR`` of the fastest are treated as a measurement tie and
    the largest chunk among them wins (at ``chunk >= N`` the scan
    degenerates to the single fused program — the noise-robust choice
    at compute-bound points where streaming has nothing to win). With
    an existing cache entry and ``force=False`` this is a read (no
    measurement).
    """
    point = _resolve_point(op, N, D, L, M, dtype, backend, impl, T)
    path = cache_path or default_cache_path()
    payload = load_cache(path)
    if not force:
        hit = payload["entries"].get(point.key)
        if hit is not None:
            return dict(hit["config"])
    cands = candidates(point)
    kept, pruned = roofline_prune(point, cands, factor=prune_factor)
    results = measure_candidates(point, kept, repeats=repeats)
    best = results[0]
    if point.impl == "scan" and len(results) > 1:
        # candidates within timing noise of the best are ties: prefer
        # the largest chunk among them — fewer scan steps, and at
        # chunk >= N the scan degenerates to the single fused program,
        # which cannot lose to the unfused pipeline it is identical to
        tol = TIE_FACTOR * best["wall_ms"]
        near = [r for r in results if r["wall_ms"] <= tol]
        best = max(near, key=lambda r: r["config"]["chunk"])
    # deep-copy the payload before mutating: load_cache may return the
    # process-wide cached object
    payload = json.loads(json.dumps(payload))
    payload["entries"][point.key] = {
        "config": best["config"],
        "wall_ms": best["wall_ms"],
        "jax": jax.__version__,
        "backend": point.backend,
        "candidates": len(cands),
        "pruned": len(pruned),
        "sweep": results,
    }
    _save_cache(payload, path)
    return dict(best["config"])


# ---------------------------------------------------------------------------
# Dispatcher integration
# ---------------------------------------------------------------------------


def resolve_config(
    kw: dict, tuning, *, op: str, impl: str,
    N: int, D: int, L: int, M: int, dtype,
    backend: str | None = None, cache_path: str | None = None,
    T: int = 0,
) -> dict:
    """Merge the tuning policy into a dispatcher's block kwargs.

    tuning="cached" (the default everywhere): consult the tuned cache
    — unless the caller already passed any block knob explicitly, which
    always wins. tuning="off": never consult. tuning=<dict>: use that
    config (explicit kwargs still win over it).
    """
    if tuning == "off" or tuning is None:
        return kw
    explicit = any(
        kw.get(k) is not None for k in ("chunk", "block_n", "block_l")
    )
    if isinstance(tuning, dict):
        cfg = tuning
    elif tuning == "cached":
        if explicit:
            return kw
        cfg = lookup(
            op, N, D, L, M, dtype,
            backend=backend, impl=impl, cache_path=cache_path, T=T,
        )
        if cfg is None:
            return kw
    else:
        raise ValueError(
            f'tuning must be "cached", "off" or an explicit config '
            f"dict, got {tuning!r}"
        )
    merged = dict(cfg)
    merged.update(kw)  # explicit caller kwargs win
    return merged
