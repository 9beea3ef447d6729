"""ELM serving plane: micro-batching request server with hot-swap beta.

The paper's whole premise is that every node holds a *usable* model at
every consensus round — Algorithm 2 keeps learning chunk-by-chunk while
the per-node estimates beta_i stay valid predictors. This module is the
query side of that loop:

* ``BetaStore`` — a versioned, thread-safe publication point for beta
  snapshots. ``ConsensusEngine.stream_chunk(..., publish_to=store)``
  publishes the post-consensus stacked betas after every streaming
  event; readers get an immutable ``BetaSnapshot`` (version + arrays)
  with one atomic reference read, so a publish can never be observed
  half-applied.

* ``ELMServer`` — a micro-batching front-end over the fused predict
  kernel (kernels/elm_predict.py). Requests of varying row counts are
  packed FIFO into a small set of padded batch shapes (``buckets``) so
  every launch hits a compile-once jitted program; each packed batch is
  answered by one node replica's beta (round-robin across the V node
  models, or pinned per request — the paper's "any node answers
  locally"). Oversized requests are split into max-bucket chunks and
  reassembled.

Hot-swap protocol (bounded staleness):

1. ``flush()`` re-reads the store **at most once, at flush start**; all
   batches in one flush share that snapshot. Per-request atomicity is
   therefore structural: a request (even a split oversized one) is
   answered by exactly one version, never a mix.
2. The cached snapshot is refreshed whenever the store has advanced by
   more than ``max_staleness`` versions (0 = always serve the latest
   published beta at flush time). Every response carries the version
   that produced it, and the serve-time guarantee is
   ``store.version_at_flush - response.version <= max_staleness``.
3. ``freeze()`` pins the current snapshot (publishes keep landing in
   the store but are not picked up); ``thaw()`` resumes hot-swapping.

* ``ContinuousELMServer`` — the continuous-batching mode (the idiom of
  ``examples/continuous_batching.py``, adapted to row-parallel ELM
  inference). Instead of FIFO buckets flushed on a tick, the server
  keeps one in-flight padded batch of ``slots`` rows per answering
  node: every ``step()`` admits pending rows into free slots (rows
  freed by completed requests are refilled mid-flight, and a request
  larger than the free slots is admitted *partially*, its remaining
  rows flowing into the next step), launches the compile-once fused
  predict on the padded batch, and completes whatever requests have
  all their rows served. Scheduling is deadline-aware: each request
  may carry a deadline, the packer orders pending rows by slack
  (earliest deadline first, FIFO among deadline-free requests), and a
  step whose head request would miss its deadline launches immediately
  even when the batch-fill gate (``min_fill``) says to wait.

Both servers share the int8-beta serving arm: ``beta_mode="int8"``
round-trips each served beta through the compression plane's per-tile
stochastic int8 quantizer (core/compression.int8_roundtrip, keyed
deterministically by snapshot version and node) — fewer beta bytes
for a bounded accuracy cost.

Multi-tenant mode: constructing either server over a
``serving.TenantRegistry`` (instead of a ``BetaStore``) switches it to
per-tenant serving — requests carry ``tenant=`` instead of ``node=``,
packing freely mixes tenants in one padded bucket, and each launch is
ONE stacked-beta fused predict (``kernels.elm_predict_ops.
predict_stacked``): the shared g(XW+b) row tile is computed once and
contracted against per-row gathered beta tiles from the snapshot's
(T, L, M) stacked tensor. The flush-level snapshot capture pins every
request's *per-tenant* version for the whole flush (split chunks
included), the staleness bound is per tenant
(``registry.stale_tenants``), and requests whose tenant was retired
mid-queue are rejected into ``server.rejections`` with the named
``RetiredTenantError`` instead of poisoning the flush.

The server itself is a single-dispatcher object (submit/flush from one
thread); the store is safe to publish into from another thread — the
serve-while-train loop in ``examples/elm_serving.py`` runs training
events and query traffic against the same store, and
``TenantRegistry.publisher(tenant)`` is the per-tenant
``stream_chunk(publish_to=...)`` hook.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.tenants import TenantRegistry, TenantSnapshot


# ---------------------------------------------------------------------------
# Versioned beta publication
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BetaSnapshot:
    """An immutable published model: stacked per-node betas + version."""

    version: int
    betas: jax.Array  # (V, L, M)

    @property
    def num_nodes(self) -> int:
        return self.betas.shape[0]


class BetaStore:
    """Atomic versioned publication point for consensus beta snapshots.

    ``publish`` bumps the version and swaps in a new immutable
    ``BetaSnapshot`` under a lock; ``snapshot`` is a single reference
    read (atomic in CPython), so readers never block publishers and can
    never observe a half-written update.
    """

    def __init__(self, betas=None):
        self._lock = threading.Lock()
        self._snap: BetaSnapshot | None = None
        if betas is not None:
            self.publish(betas)

    @staticmethod
    def _stack(betas) -> jax.Array:
        b = jnp.asarray(betas)
        if b.ndim == 2:  # single-model serving: V = 1
            b = b[None]
        if b.ndim != 3:
            raise ValueError(
                f"betas must be (L, M) or stacked (V, L, M), got {b.shape}"
            )
        return b

    def publish(self, betas) -> int:
        """Publish a new snapshot; returns its version (1-based)."""
        b = self._stack(betas)
        with self._lock:
            version = (self._snap.version if self._snap else 0) + 1
            self._snap = BetaSnapshot(version=version, betas=b)
            return version

    def snapshot(self) -> BetaSnapshot:
        snap = self._snap
        if snap is None:
            raise RuntimeError("BetaStore has no published betas yet")
        return snap

    @property
    def version(self) -> int:
        snap = self._snap
        return 0 if snap is None else snap.version


# ---------------------------------------------------------------------------
# Requests / responses
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PredictRequest:
    uid: int
    x: np.ndarray  # (n, D) query rows
    node: int  # which node replica answers (0 in multi-tenant mode)
    v_submit: int  # store version when the request was accepted
    t_submit: float
    tenant: object = None  # multi-tenant mode: which model answers


@dataclasses.dataclass(frozen=True)
class PredictResponse:
    uid: int
    y: np.ndarray  # (n, M)
    version: int  # beta snapshot that produced y (whole response);
    # in multi-tenant mode this is the *per-tenant* version
    node: int
    latency_s: float
    tenant: object = None


def latency_percentiles(latencies_s) -> dict:
    """{p50, p99, mean} in milliseconds from a latency list."""
    if not len(latencies_s):
        return {"p50_ms": 0.0, "p99_ms": 0.0, "mean_ms": 0.0}
    arr = np.asarray(latencies_s, np.float64) * 1e3
    return {
        "p50_ms": float(np.percentile(arr, 50)),
        "p99_ms": float(np.percentile(arr, 99)),
        "mean_ms": float(np.mean(arr)),
    }


# ---------------------------------------------------------------------------
# The server
# ---------------------------------------------------------------------------


class ELMServer:
    """Bucketed micro-batching ELM inference over a hot-swappable store.

    feature_map: a fusable map (RandomFeatureMap / RBFFeatureMap — the
      fused predict kernel runs g and the readout in one pass), any
      callable FeatureMap (materialized per batch), or None when
      requests already carry feature rows (deep-backbone heads).
    store: a ``BetaStore`` (hot-swap path) or a bare betas array
      (wrapped in a private store; still versioned).
    buckets: ascending padded row counts; each gets one compiled
      program. Requests longer than the largest bucket are split.
    max_staleness: how many published versions the served snapshot may
      trail the store by at flush time (0 = always re-read).
    beta_mode: "fp32" serves the published beta as-is; "int8"
      round-trips it through the compression plane's per-tile
      stochastic quantizer (deterministic in version and node) — the
      bytes/latency tradeoff arm.
    """

    #: p50/p99 are computed over a sliding window of this many requests
    LATENCY_WINDOW = 10_000

    BETA_MODES = ("fp32", "int8")

    def __init__(
        self,
        feature_map,
        store,
        *,
        buckets: tuple = (16, 64, 256, 1024),
        max_staleness: int = 0,
        use_kernel: bool | None = None,
        sample_fn: Callable | None = None,
        row_dtype=np.float32,
        beta_mode: str = "fp32",
        int8_tile: int = 128,
    ):
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError(f"buckets must be ascending unique, got {buckets}")
        if beta_mode not in self.BETA_MODES:
            raise ValueError(
                f"beta_mode must be one of {self.BETA_MODES}, got "
                f"{beta_mode!r}"
            )
        if int(max_staleness) < 0:
            raise ValueError(
                f"max_staleness must be >= 0 (trailing versions allowed "
                f"at flush time), got {max_staleness}"
            )
        if int(int8_tile) <= 0:
            raise ValueError(
                f"int8_tile must be a positive tile width, got {int8_tile}"
            )
        self.feature_map = feature_map
        self.registry = store if isinstance(store, TenantRegistry) else None
        if self.registry is not None:
            self.store = store  # multi-tenant mode: stacked-beta launches
        else:
            self.store = (
                store if isinstance(store, BetaStore) else BetaStore(store)
            )
        self.buckets = tuple(int(b) for b in buckets)
        self.max_staleness = int(max_staleness)
        self.use_kernel = use_kernel
        self.sample_fn = sample_fn  # optional post-map (e.g. argmax)
        self.row_dtype = np.dtype(row_dtype)  # every batch packs to this
        self.beta_mode = beta_mode
        self.int8_tile = int(int8_tile)
        self._row_dim = getattr(feature_map, "in_dim", None)  # else 1st req
        self._snap: BetaSnapshot | None = None
        self._frozen = False
        self._queue: deque[PredictRequest] = deque()
        self._leftover: list[PredictResponse] = []  # unclaimed by predict()
        self._uid = 0
        self._rr_node = 0
        self._fns: dict[int, Callable] = {}  # bucket rows -> compiled fn
        self._parts: dict[int, list] = {}  # uid -> chunks of a split req
        self._beta_q: dict[tuple, jax.Array] = {}  # (version, node) -> deq
        #: multi-tenant mode: (uid, tenant, error) for requests whose
        #: tenant left the pinned snapshot between submit and flush
        self.rejections: list[tuple] = []
        self.metrics = {
            "requests": 0, "responses": 0, "batches": 0,
            "rows": 0, "padded_rows": 0, "swaps": 0,
            "beta_bytes": 0, "rejected": 0, "latencies_s": [],
        }

    # ------------------------------------------------------------------ api

    def _coerce_rows(self, x) -> np.ndarray:
        """Validate one request's rows: (n>0, D) at the serving dtype."""
        x = np.asarray(x, dtype=self.row_dtype)
        if x.ndim == 1:
            x = x[None]
        if x.ndim != 2 or x.shape[0] == 0:
            raise ValueError(f"request must be (n>0, D) rows, got {x.shape}")
        if self._row_dim is None:
            self._row_dim = x.shape[1]
        elif x.shape[1] != self._row_dim:
            raise ValueError(
                f"request width {x.shape[1]} != serving width "
                f"{self._row_dim}"
            )
        return x

    def _next_node(self, node: int | None) -> int:
        """Round-robin over the *served* snapshot's node models.

        Uses the cached snapshot (refreshed by the bounded-staleness
        rule) rather than a fresh ``store.snapshot()`` per submit —
        the old per-request read was a lock-path hot-spot that also
        bypassed the ``max_staleness`` contract — and the rotation
        counter only ever advances by one, so a V change between
        submits re-wraps cleanly instead of skipping/repeating nodes
        under a shifting modulo base.
        """
        if node is not None:
            return node
        self._refresh_snapshot()
        node = self._rr_node % max(1, self._snap.num_nodes)
        self._rr_node = node + 1
        return node

    def _admit(self, node: int | None, tenant) -> int:
        """Validate the request's addressing mode; returns the node.

        Single-tenant (BetaStore) serving addresses a node replica
        (``node=``); multi-tenant (TenantRegistry) serving addresses a
        tenant model (``tenant=``). Mixing them raises a named error,
        and unknown/retired tenants are rejected here at submit time.
        """
        if self.registry is not None:
            if tenant is None:
                raise ValueError(
                    "tenant= is required when serving a TenantRegistry; "
                    "registered tenants: "
                    f"{sorted(map(repr, self.registry.tenant_ids))}"
                )
            if node is not None:
                raise ValueError(
                    "node= applies to single-tenant (BetaStore) serving; "
                    "this server serves a TenantRegistry — pin the model "
                    "with tenant= instead"
                )
            # raises the named Unknown/RetiredTenantError for bad ids
            self.registry.tenant_version(tenant)
            return 0
        if tenant is not None:
            raise ValueError(
                "tenant= applies to multi-tenant (TenantRegistry) "
                "serving; this server serves a BetaStore — pin the node "
                "replica with node= instead"
            )
        return self._next_node(node)

    def submit(self, x, *, node: int | None = None, tenant=None) -> int:
        """Queue one request of shape (n, D) (or (D,)); returns its uid.

        Rows are coerced to the server's ``row_dtype`` (one packed batch
        = one dtype, by contract) and D must match the feature map's
        input width (or the first request's, when the map doesn't say).
        node pins the answering replica; default round-robin across the
        store's V node models. Over a ``TenantRegistry`` pass ``tenant=``
        instead — packing freely mixes tenants in one stacked launch.
        Oversized requests are split into max-bucket chunks here and
        reassembled at flush.
        """
        node = self._admit(node, tenant)
        x = self._coerce_rows(x)
        uid = self._uid
        self._uid += 1
        self.metrics["requests"] += 1
        self.metrics["rows"] += x.shape[0]
        cap = self.buckets[-1]
        chunks = [x[s:s + cap] for s in range(0, x.shape[0], cap)]
        if len(chunks) > 1:
            self._parts[uid] = [None] * len(chunks)
        now = time.perf_counter()
        for part, chunk in enumerate(chunks):
            self._queue.append(PredictRequest(
                uid=uid if len(chunks) == 1 else (uid, part),
                x=chunk, node=node, tenant=tenant,
                v_submit=self.store.version, t_submit=now,
            ))
        return uid

    def flush(self) -> list[PredictResponse]:
        """Serve everything pending; returns responses in uid order.

        One store read for the whole flush (hot-swap point); FIFO
        packing per node into the smallest bucket that fits — in
        multi-tenant mode one "node" group mixes every tenant, so each
        packed batch is one stacked-beta launch. Includes any responses
        a ``predict()`` call served but did not claim.
        """
        queued = {r.tenant for r in self._queue if r.tenant is not None}
        self._refresh_snapshot(queued or None)
        responses = self._leftover
        self._leftover = []
        by_node: dict[int, list[PredictRequest]] = {}
        rejected: set = set()
        while self._queue:
            r = self._queue.popleft()
            if (
                self.registry is not None
                and r.tenant not in self._snap.slots
            ):
                uid = r.uid[0] if isinstance(r.uid, tuple) else r.uid
                if uid not in rejected:
                    rejected.add(uid)
                    self._reject(uid, r.tenant)
                self._parts.pop(uid, None)
                continue
            by_node.setdefault(r.node, []).append(r)
        served: list[PredictResponse] = []
        for node, reqs in by_node.items():
            for batch in self._pack(reqs):
                served.extend(self._launch(node, batch))
        served = self._reassemble(served)
        self._record_served(served)
        return sorted(responses + served, key=lambda r: r.uid)

    def _record_served(self, served: list) -> None:
        self.metrics["responses"] += len(served)
        lat = self.metrics["latencies_s"]
        lat.extend(r.latency_s for r in served)
        if len(lat) > self.LATENCY_WINDOW:  # long-running servers: bound it
            del lat[: len(lat) - self.LATENCY_WINDOW]

    def predict(self, x, *, node: int | None = None,
                tenant=None) -> np.ndarray:
        """Synchronous single-request convenience: submit + flush.

        Other requests pending at call time are served by the same
        flush; their responses are retained and returned by the next
        ``flush()`` rather than dropped.
        """
        uid = self.submit(x, node=node, tenant=tenant)
        mine = None
        for r in self.flush():
            if r.uid == uid:
                mine = r
            else:
                self._leftover.append(r)
        assert mine is not None
        return mine.y

    def freeze(self):
        """Pin the current snapshot; publishes are no longer picked up."""
        self._refresh_snapshot()
        self._frozen = True

    def thaw(self):
        self._frozen = False

    @property
    def served_version(self) -> int:
        return 0 if self._snap is None else self._snap.version

    def stats(self) -> dict:
        """Aggregate serving metrics incl. p50/p99 latency + padding."""
        m = dict(self.metrics)
        lat = m.pop("latencies_s")
        m.update(latency_percentiles(lat))
        total = m["rows"] + m["padded_rows"]
        m["padding_frac"] = m["padded_rows"] / total if total else 0.0
        return m

    # ------------------------------------------------------------- internals

    def _refresh_snapshot(self, tenants=None):
        """Bounded-staleness hot-swap point.

        Single-tenant: refresh when the store's global version trails by
        more than ``max_staleness``. Multi-tenant: per-tenant rule — a
        tenant that keeps publishing cannot pin everyone else's snapshot
        fresh, so refresh only when a *served* tenant (``tenants``, or
        any when None) is stale or missing from the snapshot.
        """
        if self._snap is None:
            self._snap = self.store.snapshot()
            return
        if self._frozen:
            return
        if self.registry is not None:
            stale = set(self.registry.stale_tenants(
                self._snap, self.max_staleness
            ))
            if tenants is not None:
                stale &= set(tenants)
            if stale:
                self._snap = self.store.snapshot()
                self.metrics["swaps"] += 1
            return
        latest = self.store.version
        if latest - self._snap.version > self.max_staleness:
            self._snap = self.store.snapshot()
            self.metrics["swaps"] += 1

    def _reject(self, uid, tenant) -> None:
        """Record a request whose tenant left the pinned snapshot
        between submit and flush: the named error lands in
        ``self.rejections`` instead of poisoning the whole flush
        (submit() already rejects unknown/retired tenants eagerly)."""
        try:
            self._snap._check(tenant)
        except KeyError as err:  # Unknown/RetiredTenantError
            self.rejections.append((uid, tenant, err))
            self.metrics["rejected"] += 1
            return
        raise AssertionError("rejected a servable tenant")

    def _pack(self, reqs: list) -> list[list]:
        """FIFO-pack requests into batches of <= max-bucket total rows."""
        batches, cur, rows = [], [], 0
        cap = self.buckets[-1]
        for r in reqs:
            if cur and rows + r.x.shape[0] > cap:
                batches.append(cur)
                cur, rows = [], 0
            cur.append(r)
            rows += r.x.shape[0]
        if cur:
            batches.append(cur)
        return batches

    def _bucket_for(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        raise AssertionError("packing exceeded the largest bucket")

    def _compiled(self, bucket: int) -> Callable:
        fn = self._fns.get(bucket)
        if fn is None:
            fmap, use_kernel, sample = (
                self.feature_map, self.use_kernel, self.sample_fn,
            )

            def run(xpad, beta):
                from repro.kernels import elm_predict_ops

                y = elm_predict_ops.predict_map(
                    xpad, fmap, beta, use_kernel=use_kernel
                )
                return sample(y) if sample is not None else y

            fn = self._fns[bucket] = jax.jit(run)
        return fn

    def _compiled_stacked(self, bucket: int) -> Callable:
        """Compile-once stacked-beta program for one bucket: a batch
        mixing many tenants is ONE fused launch, no per-tenant
        recompilation (re-traced only when the snapshot's tenant count
        changes the stacked tensor's shape)."""
        key = ("stacked", bucket)
        fn = self._fns.get(key)
        if fn is None:
            fmap, use_kernel, sample = (
                self.feature_map, self.use_kernel, self.sample_fn,
            )

            def run(xpad, betas, tids):
                from repro.kernels import elm_predict_ops

                y = elm_predict_ops.predict_stacked(
                    xpad, fmap, betas, tids, use_kernel=use_kernel
                )
                return sample(y) if sample is not None else y

            fn = self._fns[key] = jax.jit(run)
        return fn

    def _stacked_for(self, snap: TenantSnapshot) -> jax.Array:
        """The served stacked (T, L, M) tensor: published, or its int8
        round-trip (deterministic in the snapshot version; cached so
        repeated launches pay quantization once per snapshot)."""
        if self.beta_mode == "fp32":
            return snap.betas
        key = (snap.version, "stacked")
        deq = self._beta_q.get(key)
        if deq is None:
            from repro.core.compression import (
                CompressionSpec, int8_roundtrip,
            )

            betas = snap.betas.astype(jnp.float32)
            flat = int8_roundtrip(
                betas.reshape(-1), self.int8_tile,
                jax.random.key(snap.version),
            )
            deq = flat.reshape(betas.shape)
            self._beta_q = {
                k: v for k, v in self._beta_q.items()
                if k[0] == snap.version
            }
            self._beta_q[key] = deq
            self.metrics["beta_bytes"] += CompressionSpec(
                mode="int8", tile=self.int8_tile
            ).message_bytes(int(betas.size))
        return deq

    def _beta_for(self, snap: BetaSnapshot, node: int) -> jax.Array:
        """The served beta for one node: published, or its int8
        round-trip (deterministic in version and node; cached per
        snapshot so repeated launches pay quantization once)."""
        idx = node % snap.num_nodes
        if self.beta_mode == "fp32":
            return snap.betas[idx]
        key = (snap.version, idx)
        deq = self._beta_q.get(key)
        if deq is None:
            from repro.core.compression import (
                CompressionSpec, int8_roundtrip,
            )

            beta = snap.betas[idx].astype(jnp.float32)
            flat = int8_roundtrip(
                beta.reshape(-1), self.int8_tile,
                jax.random.fold_in(jax.random.key(snap.version), idx),
            )
            deq = flat.reshape(beta.shape)
            # hold only the live snapshot's quantized betas
            self._beta_q = {
                k: v for k, v in self._beta_q.items()
                if k[0] == snap.version
            }
            self._beta_q[key] = deq
            self.metrics["beta_bytes"] += CompressionSpec(
                mode="int8", tile=self.int8_tile
            ).message_bytes(int(beta.size))
        return deq

    def _launch(self, node: int, batch: list) -> list[PredictResponse]:
        snap = self._snap
        rows = sum(r.x.shape[0] for r in batch)
        bucket = self._bucket_for(rows)
        X = np.zeros((bucket, batch[0].x.shape[1]), batch[0].x.dtype)
        off = 0
        for r in batch:
            X[off:off + r.x.shape[0]] = r.x
            off += r.x.shape[0]
        if self.registry is not None:
            # one stacked launch mixes every tenant in the batch; the
            # padded tail rows carry slot 0 (their hidden rows are
            # masked to zero, so the gathered beta contributes nothing)
            tids = np.zeros((bucket,), np.int32)
            off = 0
            for r in batch:
                tids[off:off + r.x.shape[0]] = snap.slot(r.tenant)
                off += r.x.shape[0]
            Y = np.asarray(self._compiled_stacked(bucket)(
                jnp.asarray(X), self._stacked_for(snap), jnp.asarray(tids)
            ))
        else:
            beta = self._beta_for(snap, node)
            Y = np.asarray(self._compiled(bucket)(jnp.asarray(X), beta))
        self.metrics["batches"] += 1
        self.metrics["padded_rows"] += bucket - rows
        now = time.perf_counter()
        out, off = [], 0
        for r in batch:
            n = r.x.shape[0]
            if self.registry is not None:
                # the flush-level snapshot pins every request's
                # per-tenant version for the whole flush
                version, rnode = snap.tenant_version(r.tenant), 0
            else:
                version, rnode = snap.version, node % snap.num_nodes
            out.append(PredictResponse(
                uid=r.uid, y=Y[off:off + n], version=version,
                node=rnode, tenant=r.tenant, latency_s=now - r.t_submit,
            ))
            off += n
        return out

    def _reassemble(self, responses: list) -> list[PredictResponse]:
        """Merge split-request chunk responses back into whole ones."""
        whole, pending = [], {}
        for r in responses:
            if isinstance(r.uid, tuple):
                uid, part = r.uid
                self._parts[uid][part] = r
                pending[uid] = True
            else:
                whole.append(r)
        for uid in pending:
            parts = self._parts.pop(uid)
            assert all(p is not None for p in parts)
            versions = {p.version for p in parts}
            # structural guarantee: one snapshot per flush, split chunks
            # are always flushed together
            assert len(versions) == 1, "split request straddled versions"
            whole.append(PredictResponse(
                uid=uid,
                y=np.concatenate([p.y for p in parts], axis=0),
                version=parts[0].version,
                node=parts[0].node,
                tenant=parts[0].tenant,
                latency_s=max(p.latency_s for p in parts),
            ))
        return whole


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Pending:
    """One admitted-but-unfinished request in the continuous server."""

    uid: int
    x: np.ndarray
    node: int
    deadline: float | None
    t_submit: float
    tenant: object = None  # multi-tenant mode: which model answers
    served: list = dataclasses.field(default_factory=list)
    offset: int = 0  # rows already served (mid-flight when 0 < offset < n)
    version: int | None = None  # pinned at the request's first launch

    @property
    def remaining(self) -> int:
        return self.x.shape[0] - self.offset

    @property
    def slack_key(self) -> tuple:
        """EDF order: earliest deadline first, FIFO among deadline-free."""
        return (
            self.deadline if self.deadline is not None else float("inf"),
            self.uid,
        )


class ContinuousELMServer(ELMServer):
    """Continuous-batching ELM inference: admit every step, refill
    freed slots mid-flight, schedule by deadline slack.

    Where ``ELMServer`` packs FIFO into padded buckets and serves only
    on ``flush()``, this server keeps one in-flight padded batch of
    ``slots`` rows per answering node and advances it with ``step()``:

    1. **Admission.** Pending requests are ordered by slack (earliest
       deadline first; deadline-free requests FIFO behind them) and
       their rows admitted into free slots. A request larger than the
       free slots is admitted *partially* — its remaining rows flow
       into the next step's batch, occupying slots freed by requests
       that completed (the mid-flight refill of
       ``examples/continuous_batching.py``, at row granularity).
    2. **Launch gate.** A step launches when ``force=True``, when any
       request is already mid-flight (never stall started work), when
       at least ``min_fill * slots`` rows are ready, or when the head
       request's slack has run out (``deadline - now <=
       deadline_slack_s``) — the deadline-aware force flush. An
       ungated step with too few rows returns [] and waits for more
       traffic. ``min_fill=0`` (default) always launches.
    3. **Completion.** Requests whose rows are all served complete
       immediately; their slots are free for the next step.

    Hot-swap protocol: the snapshot is re-read (same bounded-staleness
    rule as ``ELMServer``) only at steps where *no* request is
    mid-flight, and each request pins the version of its first launch —
    so a request is answered by exactly one beta version even when its
    rows span steps and publishes land in between.

    ``flush()`` force-steps until drained (same contract as
    ``ELMServer.flush``: responses in uid order, leftovers included),
    so ``predict()`` works unchanged. ``clock`` injects a time source
    for deterministic deadline tests.
    """

    def __init__(
        self,
        feature_map,
        store,
        *,
        slots: int = 256,
        min_fill: float = 0.0,
        deadline_slack_s: float = 0.0,
        clock: Callable[[], float] = time.perf_counter,
        **kw,
    ):
        if int(slots) <= 0:
            raise ValueError(
                f"slots must be a positive in-flight row count, got "
                f"{slots}"
            )
        if float(deadline_slack_s) < 0.0:
            raise ValueError(
                f"deadline_slack_s must be >= 0 seconds, got "
                f"{deadline_slack_s}"
            )
        super().__init__(feature_map, store, buckets=(int(slots),), **kw)
        if not 0.0 <= float(min_fill) <= 1.0:
            raise ValueError(f"min_fill must be in [0, 1], got {min_fill}")
        self.slots = int(slots)
        self.min_fill = float(min_fill)
        self.deadline_slack_s = float(deadline_slack_s)
        self.clock = clock
        self._pending: list[_Pending] = []
        self.metrics["steps"] = 0
        self.metrics["deadline_flushes"] = 0

    # ------------------------------------------------------------------ api

    def submit(self, x, *, node: int | None = None, tenant=None,
               deadline: float | None = None) -> int:
        """Queue one request; rows are admitted continuously by step().

        deadline: absolute time (on the server's ``clock``) by which
        the request should be served; orders admission (EDF) and
        force-launches partial batches about to miss. None = FIFO
        behind all deadlined requests. Over a ``TenantRegistry`` pass
        ``tenant=`` instead of ``node=``.
        """
        node = self._admit(node, tenant)
        x = self._coerce_rows(x)
        uid = self._uid
        self._uid += 1
        self.metrics["requests"] += 1
        self.metrics["rows"] += x.shape[0]
        self._pending.append(_Pending(
            uid=uid, x=x, node=node, tenant=tenant,
            deadline=None if deadline is None else float(deadline),
            t_submit=self.clock(),
        ))
        return uid

    def step(self, *, force: bool = False) -> list[PredictResponse]:
        """One admission + launch cycle; returns completed responses."""
        if not self._pending:
            return []
        now = self.clock()
        mid_flight = any(p.offset > 0 for p in self._pending)
        if not mid_flight:
            # refresh only between requests: every row of a request is
            # served by the version pinned at its first launch
            self._refresh_snapshot(
                {p.tenant for p in self._pending}
                if self.registry is not None else None
            )
            if self.registry is not None:
                # nothing is mid-flight, so every pending request is
                # still unstarted: reject the ones whose tenant left
                # the fresh snapshot (named error in self.rejections)
                keep = []
                for p in self._pending:
                    if p.tenant in self._snap.slots:
                        keep.append(p)
                    else:
                        self._reject(p.uid, p.tenant)
                self._pending = keep
                if not self._pending:
                    return []
        self._pending.sort(key=lambda p: p.slack_key)
        head = self._pending[0]
        ready = sum(p.remaining for p in self._pending)
        head_would_miss = (
            head.deadline is not None
            and head.deadline - now <= self.deadline_slack_s
        )
        launch = (
            force
            or mid_flight
            or ready >= self.min_fill * self.slots
            or head_would_miss
        )
        if not launch:
            return []
        if head_would_miss and ready < self.min_fill * self.slots:
            self.metrics["deadline_flushes"] += 1
        # admit rows (EDF order) into per-node batches of <= slots rows
        batches: dict[int, list[tuple[_Pending, int, int]]] = {}
        fill: dict[int, int] = {}
        snap = self._snap
        for p in self._pending:
            if (
                self.registry is not None
                and p.tenant not in snap.slots
            ):
                # admitted while another request was mid-flight and the
                # pinned snapshot predates this tenant: wait for the
                # next refresh point (retired tenants are rejected
                # there instead)
                continue
            free = self.slots - fill.get(p.node, 0)
            take = min(free, p.remaining)
            if take <= 0:
                continue
            batches.setdefault(p.node, []).append((p, p.offset, take))
            fill[p.node] = fill.get(p.node, 0) + take
            p.offset += take
        for node, parts in batches.items():
            X = np.zeros((self.slots, parts[0][0].x.shape[1]),
                         self.row_dtype)
            tids = np.zeros((self.slots,), np.int32)
            off = 0
            for p, start, take in parts:
                X[off:off + take] = p.x[start:start + take]
                if self.registry is not None:
                    tids[off:off + take] = snap.slot(p.tenant)
                off += take
            if self.registry is not None:
                Y = np.asarray(self._compiled_stacked(self.slots)(
                    jnp.asarray(X), self._stacked_for(snap),
                    jnp.asarray(tids),
                ))
            else:
                Y = np.asarray(self._compiled(self.slots)(
                    jnp.asarray(X), self._beta_for(snap, node)
                ))
            self.metrics["batches"] += 1
            self.metrics["padded_rows"] += self.slots - off
            off = 0
            for p, _, take in parts:
                if p.version is None:
                    # multi-tenant mode pins the *per-tenant* version
                    # of the request's first launch
                    p.version = (
                        snap.tenant_version(p.tenant)
                        if self.registry is not None else snap.version
                    )
                p.served.append(Y[off:off + take])
                off += take
        self.metrics["steps"] += 1
        done_at = self.clock()
        completed = []
        still = []
        for p in self._pending:
            if p.remaining == 0:
                completed.append(PredictResponse(
                    uid=p.uid,
                    y=np.concatenate(p.served, axis=0),
                    version=p.version,
                    node=(
                        0 if self.registry is not None
                        else p.node % snap.num_nodes
                    ),
                    tenant=p.tenant,
                    latency_s=done_at - p.t_submit,
                ))
            else:
                still.append(p)
        self._pending = still
        completed.sort(key=lambda r: r.uid)
        self._record_served(completed)
        return completed

    def flush(self) -> list[PredictResponse]:
        """Force-step until drained; responses in uid order (plus any
        leftovers a ``predict()`` call served but did not claim)."""
        responses = self._leftover
        self._leftover = []
        while self._pending:
            responses.extend(self.step(force=True))
        return sorted(responses, key=lambda r: r.uid)

    def stats(self) -> dict:
        m = super().stats()
        m["pending_rows"] = sum(p.remaining for p in self._pending)
        return m
