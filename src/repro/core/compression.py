"""Compressed gossip: quantized / sparsified wire payloads with
CHOCO-style error feedback, event-triggered rounds, and exact
bytes-on-wire accounting.

The paper motivates DC-ELM for networks where "the amount of
information exchanging" is the binding constraint (Sec. V). The inline
mixer knob (``compress="bf16"``) halves the payload; this module is the
aggressive end of that axis.

**The replica scheme.** Naively quantizing the broadcast state leaves a
noise floor set by the *full* payload magnitude (the per-tile scale is
max|beta|/127 no matter how converged the network is). Instead, every
node maintains a public replica x̂_i — what its neighbors have
reconstructed about it — and each round transmits only the encoded
difference

    q_i = Q(x_i - x̂_i),   x̂_i <- x̂_i + q_i ,

while receivers integrate the same q_i into their copy of x̂_i and the
consensus Laplacian is formed over replicas:
lap_i = sum_j a_ij (x̂_j - x̂_i). This is CHOCO-gossip's error-feedback
memory: the residual x_i - x̂_i is exactly the information not yet
transmitted, it is carried in the engine state, and the quantizer's
per-tile scale *decays with it* — so int8 (even top-k) gossip
converges to the exact consensus instead of a quantization floor, and
the Thm. 2 contraction survives because the replica lag
||x - x̂|| = ||d - Q(d)|| is a contraction of the residual itself.

**Event-triggered rounds.** With ``event_threshold`` set, a node whose
residual RMS is below the threshold broadcasts nothing at all (zero
bytes; receivers' replicas simply don't move — skipping is a no-op, not
an error). Because residuals decay to zero, a converged network goes
*silent*, which is what makes compressed gossip pay off in reach-and-
hold serving windows and Algorithm 2 streaming.

**Faults.** Replica updates are incremental, so delta messages must
not be silently *lost* — the transport is modeled as reliable links
with outages (``FaultyMixer``): while a link is down its mix term is
gated to zero exactly as in the uncompressed fault layer, undelivered
deltas queue, and the queue flushes on recovery (one catch-up message,
since a sum of deltas is itself one delta). Every live receiver
therefore holds the same reconstruction x̂_j, and the compressed
Laplacian is simply the base mixer's (masked, time-varying, ...)
Laplacian evaluated over replicas instead of raw states.

``refresh_every=N`` additionally makes every N-th round an absolute
broadcast (same wire format, applied by assignment) for deployments
whose transport cannot guarantee delivery; ``error_feedback=False`` is
the memoryless ablation — every round an absolute broadcast — which
reproduces the classic quantize-the-state scheme and its bias floor.

See DESIGN.md §9 and ``examples/compressed_gossip.py``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import gossip
from repro.core.mixers import DenseMixer, FaultyMixer, PpermuteMixer
from repro.utils import compat

MODES = ("none", "bf16", "int8", "topk")


@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """Declarative wire format for gossip payloads.

    mode:   "none" | "bf16" | "int8" | "topk".
    tile:   int8 only — values sharing one f32 scale (max|x|/127 over
            the tile; 4 bytes of header on the wire per tile).
    k:      topk only — kept entries per message, as a fraction of the
            payload (float in (0, 1]) or an absolute count (int). Each
            kept entry ships its value plus a 4-byte index.
    error_feedback: CHOCO replica memory (see module docstring). False
            degrades to memoryless absolute quantization every round —
            the ablation showing the quantization-bias floor.
    event_threshold: skip a node's broadcast entirely when the RMS of
            its untransmitted residual x - x̂ is below this; None
            broadcasts every round. Skipped broadcasts cost 0 bytes.
    refresh_every: every N-th round is an absolute (non-incremental)
            broadcast that resynchronizes receiver replicas — required
            for exactness under fault traces; 0 never refreshes.
    seed:   PRNG stream for int8 stochastic rounding. Encoding is
            deterministic in (seed, round, node), so the simulated and
            sharded paths quantize identically and can be compared.
    """

    mode: str = "none"
    tile: int = 128
    k: float | int = 0.1
    error_feedback: bool = True
    event_threshold: float | None = None
    refresh_every: int = 0
    seed: int = 0

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"unknown compression mode {self.mode!r}: expected one of "
                f"{MODES}"
            )
        if self.mode == "int8" and self.tile < 1:
            raise ValueError(f"int8 tile must be >= 1, got {self.tile}")
        if self.mode == "topk":
            if isinstance(self.k, float) and not 0.0 < self.k <= 1.0:
                raise ValueError(
                    f"topk fraction must be in (0, 1], got {self.k}"
                )
            if isinstance(self.k, int) and self.k < 1:
                raise ValueError(f"topk count must be >= 1, got {self.k}")
        if self.refresh_every < 0:
            raise ValueError(
                f"refresh_every must be >= 0, got {self.refresh_every}"
            )
        if self.event_threshold is not None and not self.error_feedback:
            raise ValueError(
                "event_threshold requires error_feedback: without the "
                "replica memory every round is an absolute broadcast "
                "(effective_refresh == 1), which forces every node to "
                "send and silently disables event triggering"
            )

    @classmethod
    def parse(cls, value) -> "CompressionSpec":
        """Normalize ``None`` / a mode string / a spec into a spec."""
        if value is None:
            return cls(mode="none")
        if isinstance(value, cls):
            return value
        if isinstance(value, str):
            return cls(mode=value)
        raise TypeError(
            f"compression must be None, a mode string {MODES}, or a "
            f"CompressionSpec, got {type(value).__name__}"
        )

    @property
    def is_identity(self) -> bool:
        return self.mode == "none" and self.event_threshold is None

    @property
    def effective_refresh(self) -> int:
        """Rounds between absolute broadcasts (1 = memoryless)."""
        if not self.error_feedback:
            return 1
        return self.refresh_every

    def topk_count(self, num_values: int) -> int:
        if isinstance(self.k, float):
            return max(1, min(num_values, round(self.k * num_values)))
        return min(num_values, self.k)

    def message_bytes(self, num_values: int, itemsize: int = 4) -> int:
        """Exact bytes one encoded message of ``num_values`` costs on
        the wire (payload + headers)."""
        if self.mode == "none":
            return num_values * itemsize
        if self.mode == "bf16":
            return num_values * 2
        if self.mode == "int8":
            # int8 codes + one f32 scale per tile
            return num_values + 4 * math.ceil(num_values / self.tile)
        # topk: kept values at state precision + int32 indices
        return self.topk_count(num_values) * (itemsize + 4)


# ---------------------------------------------------------------------------
# Encoders (the receiver's dequantized view; exact wire cost is accounted
# separately via CompressionSpec.message_bytes)
# ---------------------------------------------------------------------------


def int8_roundtrip(flat: jax.Array, tile: int, key: jax.Array) -> jax.Array:
    """Stochastically quantize a flat payload to int8 with per-tile
    scales and dequantize — the receiver's view of the message.

    Per tile of ``tile`` values: scale = max|x|/127, codes
    floor(x/scale + u) with u ~ U[0,1) (unbiased stochastic rounding),
    clipped to [-127, 127]. All-zero tiles round-trip exactly (scale 0
    encodes the zero code).
    """
    n = flat.shape[0]
    pad = (-n) % tile
    t = jnp.pad(flat, (0, pad)).reshape(-1, tile)
    amax = jnp.max(jnp.abs(t), axis=1, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
    u = jax.random.uniform(key, t.shape, dtype=t.dtype)
    q = jnp.clip(jnp.floor(t / safe + u), -127.0, 127.0)
    deq = q * jnp.where(scale > 0, scale, jnp.zeros_like(scale))
    return deq.reshape(-1)[:n]


def topk_roundtrip(flat: jax.Array, count: int) -> jax.Array:
    """Keep exactly the ``count`` largest-magnitude entries, zero the
    rest. Ties break toward the lower index (stable argsort), so the
    kept set matches what ``message_bytes`` bills and is identical on
    the simulated and sharded paths.
    """
    idx = jnp.argsort(-jnp.abs(flat), stable=True)[:count]
    mask = jnp.zeros(flat.shape, jnp.bool_).at[idx].set(True)
    return jnp.where(mask, flat, jnp.zeros_like(flat))


def encode_flat(flat: jax.Array, spec: CompressionSpec, key) -> jax.Array:
    """Encode+decode one node's flat payload under ``spec``."""
    if spec.mode == "none":
        return flat
    if spec.mode == "bf16":
        return flat.astype(jnp.bfloat16).astype(flat.dtype)
    if spec.mode == "int8":
        return int8_roundtrip(flat, spec.tile, key)
    return topk_roundtrip(flat, spec.topk_count(flat.shape[0]))


def encode_tree(h, spec: CompressionSpec, key):
    """Encode one node's payload pytree, leaf keys folded from ``key``."""
    leaves, treedef = jax.tree.flatten(h)
    out = [
        encode_flat(
            v.reshape(-1), spec, jax.random.fold_in(key, i)
        ).reshape(v.shape)
        for i, v in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, out)


def residual_rms(d) -> jax.Array:
    """RMS of a residual pytree (the event-trigger statistic)."""
    leaves = jax.tree.leaves(d)
    sq = sum(jnp.sum(v.astype(jnp.float32) ** 2) for v in leaves)
    n = sum(v.size for v in leaves)
    return jnp.sqrt(sq / n)


# ---------------------------------------------------------------------------
# Wire accounting
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WireStats:
    """Exact bytes-on-wire for one consensus run.

    A "link" is one directed live edge in one round; each link moves
    one encoded message unless its sender was event-gated silent.
    ``bytes_uncompressed`` is what the same live links would have moved
    at full state precision with every broadcast sent — the
    uncompressed baseline for compression ratios.
    """

    rounds: int
    links_live: int
    links_sent: int
    bytes_on_wire: int
    bytes_uncompressed: int
    per_round_bytes: np.ndarray = dataclasses.field(compare=False)

    @property
    def links_skipped(self) -> int:
        return self.links_live - self.links_sent

    @property
    def compression_ratio(self) -> float:
        """bytes_on_wire / bytes_uncompressed (lower is better)."""
        if self.bytes_uncompressed == 0:
            return 1.0
        return self.bytes_on_wire / self.bytes_uncompressed

    def __add__(self, other: "WireStats") -> "WireStats":
        return WireStats(
            rounds=self.rounds + other.rounds,
            links_live=self.links_live + other.links_live,
            links_sent=self.links_sent + other.links_sent,
            bytes_on_wire=self.bytes_on_wire + other.bytes_on_wire,
            bytes_uncompressed=(
                self.bytes_uncompressed + other.bytes_uncompressed
            ),
            per_round_bytes=np.concatenate(
                [self.per_round_bytes, other.per_round_bytes]
            ),
        )


def payload_sizes(x, num_nodes: int) -> list[tuple[int, int]]:
    """Per-leaf (values_per_node, itemsize) for a stacked state pytree."""
    sizes = []
    for v in jax.tree.leaves(x):
        if v.shape[0] != num_nodes:
            raise ValueError(
                f"stacked leaf {v.shape} has no leading node axis of "
                f"{num_nodes}"
            )
        sizes.append((v.size // num_nodes, jnp.dtype(v.dtype).itemsize))
    return sizes


def node_message_bytes(
    spec: CompressionSpec, sizes: list[tuple[int, int]]
) -> tuple[int, int]:
    """(encoded, full-precision) bytes of one node's broadcast."""
    enc = sum(spec.message_bytes(n, itemsize) for n, itemsize in sizes)
    raw = sum(n * itemsize for n, itemsize in sizes)
    return enc, raw


def stats_from_links(
    out_degree: np.ndarray,
    num_iters: int,
    msg_bytes: int,
    raw_bytes: int,
    sent: np.ndarray | None = None,
    start: int = 0,
) -> WireStats:
    """Assemble WireStats from per-round live out-degrees.

    out_degree: (R, V) live outgoing links per node, replayed k % R
    starting at absolute round ``start``.
    sent: (num_iters, V) 0/1 broadcast flags; None = always sent.
    """
    out_degree = np.asarray(out_degree, dtype=np.int64)
    rows = out_degree[
        (start + np.arange(num_iters)) % out_degree.shape[0]
    ]
    live = rows.sum(axis=1)
    if sent is None:
        sent_links = live
    else:
        sent_links = (rows * np.asarray(sent, dtype=np.int64)).sum(axis=1)
    return WireStats(
        rounds=num_iters,
        links_live=int(live.sum()),
        links_sent=int(sent_links.sum()),
        bytes_on_wire=int(sent_links.sum()) * msg_bytes,
        bytes_uncompressed=int(live.sum()) * raw_bytes,
        per_round_bytes=sent_links * msg_bytes,
    )


def dense_out_degrees(adjacencies) -> np.ndarray:
    """(S, V) live out-degree table of dense adjacency snapshots."""
    adj = np.asarray(adjacencies)
    return (adj != 0).sum(axis=2).astype(np.int64)


def record_wire_stats(mixer, stats: WireStats | None) -> None:
    """Store a run's WireStats on a mixer — the one place the storage
    convention lives (uses ``object.__setattr__`` so frozen-dataclass
    mixers work too). The stats are counted from shapes when ``run`` is
    traced, so under ``jax.jit`` they describe the program, once, and
    are not a tally of the rounds it later executes."""
    object.__setattr__(mixer, "last_wire_stats", stats)


def compute_wire_stats(
    compress,
    out_degree: np.ndarray,
    x,
    num_nodes: int,
    num_iters: int,
    sent: np.ndarray | None = None,
    start: int = 0,
) -> WireStats | None:
    """The one wire-accounting body every mixer records through.

    compress: anything ``CompressionSpec.parse`` accepts (the inline
    mixer knob or a full spec). Returns None for states without a
    stacked node axis (nothing sensible to bill). Shape-only — safe
    under tracing, costs nothing on device.
    """
    spec = CompressionSpec.parse(compress)
    try:
        sizes = payload_sizes(x, num_nodes)
    except ValueError:  # state without a stacked node axis
        return None
    msg, raw = node_message_bytes(spec, sizes)
    return stats_from_links(out_degree, num_iters, msg, raw, sent, start)


# ---------------------------------------------------------------------------
# CompressedMixer
# ---------------------------------------------------------------------------


class CompressedMixer:
    """Compression wrapper: a base mixer plus a ``CompressionSpec``.

    Composes over ``DenseMixer``, ``PpermuteMixer``, or a
    ``FaultyMixer`` wrapping either (``engine.with_faults`` stacks the
    two in that order automatically). Per round, each node

    1. forms its residual d_i = x_i - x̂_i against its public replica;
    2. decides to broadcast: always, or — event-triggered — only when
       ``residual_rms(d_i) > event_threshold`` (refresh rounds always
       broadcast);
    3. encodes q_i = Q(d_i) (or Q(x_i) on a refresh round) — the
       encode happens *before* the wire, so only encoded messages
       cross a link — and every replica of node i (its own and its
       receivers', reliable-transport model) advances by q_i;
    4. mixes over replicas: lap_i = sum_j a_ij (x̂_j - x̂_i) is the
       *base* mixer's Laplacian evaluated at x̂, so fault masks and
       time-varying snapshots gate terms exactly like the uncompressed
       path.

    The compiled ``shard_map(scan)`` program is cached (keyed by
    rule/rounds/specs) so streaming events and spec sweeps compile
    once. ``run`` records exact wire accounting on
    ``self.last_wire_stats`` (surfaced as ``ConsensusEngine.wire_stats``).

    ``laplacian``/``step`` are stateless (each call behaves like a
    refresh round: absolute encode, no replicas, no event gating); the
    replica-carrying iteration lives in ``run``. The replica memory and
    the absolute round counter persist across ``run``/``stream_chunk``
    calls on this mixer (x̂ is protocol state: a converged-and-quiet
    network stays quiet across streaming events, and blocked runs
    continue the PRNG / fault-trace / refresh streams); a state whose
    shapes change, or ``reset_replicas()``, cold-starts them.
    """

    def __init__(self, base, spec):
        self.spec = CompressionSpec.parse(spec)
        if not isinstance(base, (DenseMixer, PpermuteMixer, FaultyMixer)):
            raise TypeError(
                f"CompressedMixer wraps DenseMixer, PpermuteMixer, or "
                f"FaultyMixer, got {type(base).__name__}"
            )
        if base.compress is not None:
            raise ValueError(
                "the base mixer already has an inline compress= knob "
                f"({base.compress!r}); set it to None and express the "
                "wire format in the CompressionSpec instead"
            )
        self.base = base
        self.last_wire_stats: WireStats | None = None
        self._programs: dict = {}
        # replica memory persists across run()/stream_chunk() calls on
        # this mixer: x̂ is real protocol state (what the network has
        # already been told), so a converged-and-quiet network STAYS
        # quiet across streaming events, and blocked runs continue the
        # PRNG / fault-trace / refresh streams instead of restarting
        # them. reset_replicas() forgets both.
        self._replica = None
        self._rounds_done = 0

    def reset_replicas(self) -> None:
        """Forget the replica memory and the absolute round counter
        (e.g. to replay a run from a cold network)."""
        self._replica = None
        self._rounds_done = 0

    def _initial_replicas(self, x):
        """(x̂0, absolute start round) for this run — the persisted
        state when it matches ``x``'s structure, else a cold start."""
        if self._replica is not None:
            prev = jax.tree.leaves(self._replica)
            cur = jax.tree.leaves(x)
            if (
                jax.tree.structure(self._replica) == jax.tree.structure(x)
                and len(prev) == len(cur)
                and all(
                    p.shape == c.shape and p.dtype == c.dtype
                    for p, c in zip(prev, cur)
                )
            ):
                return self._replica, self._rounds_done
        return jax.tree.map(jnp.zeros_like, x), 0

    # -- delegation --------------------------------------------------------

    @property
    def num_nodes(self) -> int:
        return self.base.num_nodes

    @property
    def compress(self):
        return self.base.compress  # always None; the spec supersedes it

    def gamma_upper_bound(self) -> float:
        return self.base.gamma_upper_bound()

    def default_gamma(self, safety: float = 0.9) -> float:
        return self.base.default_gamma(safety)

    def node_pspec(self) -> P:
        return self.base.node_pspec()

    # -- layout ------------------------------------------------------------

    @property
    def _dense_path(self) -> bool:
        base = self.base
        if isinstance(base, FaultyMixer):
            return base._dense is not None
        return isinstance(base, DenseMixer)

    @property
    def _pp(self) -> PpermuteMixer:
        base = self.base
        return base.base if isinstance(base, FaultyMixer) else base

    def _round_key(self, k):
        return jax.random.fold_in(jax.random.key(self.spec.seed), k)

    def _out_degrees(self) -> np.ndarray:
        """(R, V) live out-degree table for wire accounting."""
        base = self.base
        if isinstance(base, DenseMixer):
            return dense_out_degrees(base.adjacencies)
        if isinstance(base, FaultyMixer):
            if base._dense is not None:
                return dense_out_degrees(base._dense.adjacencies)
            # folded keep is (R, P, V) in-edge weights; symmetric masks
            # on undirected perms make in-degree == out-degree
            return (
                (np.asarray(base._keep) != 0).sum(axis=1).astype(np.int64)
            )
        sizes = self._pp.axis_sizes
        deg = self._pp.spec.degree(sizes)
        return np.full((1, self.num_nodes), deg, dtype=np.int64)

    def _record(
        self, x, num_iters: int, sent: np.ndarray | None, start: int = 0
    ) -> None:
        record_wire_stats(self, compute_wire_stats(
            self.spec, self._out_degrees(), x, self.num_nodes, num_iters,
            sent, start,
        ))

    # -- shared round body -------------------------------------------------

    def _send_gate(self, d, k):
        """1.0 when this node broadcasts in round k, else 0.0."""
        spec = self.spec
        one = jnp.ones(())
        if spec.event_threshold is None:
            return one
        sent = (residual_rms(d) > spec.event_threshold).astype(jnp.float32)
        N = spec.effective_refresh
        if N:
            sent = jnp.where(jnp.mod(k, N) == 0, one, sent)
        return sent

    def _refresh_flag(self, k):
        """1.0 on absolute-broadcast rounds, else 0.0 (scalar, traced)."""
        N = self.spec.effective_refresh
        if not N:
            return jnp.zeros(())
        return (jnp.mod(k, N) == 0).astype(jnp.float32)

    # -- stateless single round -------------------------------------------

    def laplacian(self, x, k=0):
        """One round's Laplacian over encoded payloads (stateless: no
        replica memory or event gating — every node absolute-encodes
        and broadcasts). On the ppermute path call inside a
        caller-managed shard_map."""
        spec = self.spec
        if spec.mode == "none":
            return self.base.laplacian(x, k)
        rk = self._round_key(k)
        if self._dense_path:
            V = self.num_nodes
            keys = jax.vmap(lambda i: jax.random.fold_in(rk, i))(
                jnp.arange(V)
            )
            p = jax.vmap(lambda h, key: encode_tree(h, spec, key))(x, keys)
        else:
            my = gossip.global_node_index(
                self._pp.spec, self._pp.axis_sizes
            )
            p = encode_tree(x, spec, jax.random.fold_in(rk, my))
        return self.base.laplacian(p, k)

    # -- scan drivers ------------------------------------------------------

    def run(
        self,
        rule,
        x,
        aux,
        gamma,
        num_iters: int,
        trace_fn=None,
        state_spec=None,
        aux_spec=None,
    ):
        if self.spec.is_identity:
            out = self.base.run(
                rule, x, aux, gamma, num_iters, trace_fn, state_spec,
                aux_spec,
            )
            self._record(x, num_iters, None)
            return out
        if self._dense_path:
            return self._run_dense(rule, x, aux, gamma, num_iters, trace_fn)
        return self._run_sharded(
            rule, x, aux, gamma, num_iters, trace_fn, state_spec, aux_spec
        )

    def _node_broadcast(self, xi, xhati, refresh, k, key):
        """One node's round: residual, event gate, encode. Returns
        (q, sent) — the (zero-if-silent) replica increment/refresh."""
        spec = self.spec
        di = jax.tree.map(jnp.subtract, xi, xhati)
        # absolute broadcast on refresh rounds, delta otherwise
        src = jax.tree.map(
            lambda dv, xv: refresh * xv + (1 - refresh) * dv, di, xi
        )
        sent = self._send_gate(di, k)
        q = encode_tree(src, spec, key)
        return jax.tree.map(lambda v: (sent * v).astype(v.dtype), q), sent

    def _advance_replicas(self, xhat, q, refresh):
        """x̂ <- x̂ + q (or q itself on refresh rounds). A silent node's
        q is zero, so skipping is a no-op for every replica."""
        return jax.tree.map(
            lambda h, qv: ((1 - refresh) * h + qv).astype(h.dtype), xhat, q
        )

    def _run_dense(self, rule, x, aux, gamma, num_iters, trace_fn):
        """Replica-tracking rounds on the stacked dense layout: carry
        (x, x̂), mix the *base* Laplacian over x̂."""
        V = self.num_nodes

        def round_fn(carry, k):
            x_, xhat = carry
            rk = self._round_key(k)
            keys = jax.vmap(lambda i: jax.random.fold_in(rk, i))(
                jnp.arange(V)
            )
            refresh = self._refresh_flag(k)
            q, sent = jax.vmap(
                lambda xi, hi, ki: self._node_broadcast(
                    xi, hi, refresh, k, ki
                )
            )(x_, xhat, keys)
            xhat2 = self._advance_replicas(xhat, q, refresh)
            # the base's apply_round fuses gather + rule where it can
            # (NeighborMixer -> kernels/elm_gossip_ops); the default is
            # the exact rule(x, base.laplacian(x̂, k)) composition
            nxt = self.base.apply_round(rule, x_, xhat2, aux, gamma, k)
            tr = trace_fn(nxt) if trace_fn is not None else jnp.zeros(())
            return (nxt, xhat2), (sent, tr)

        xhat0, k0 = self._initial_replicas(x)
        (final, xhat_f), (sent, traces) = lax.scan(
            round_fn, (x, xhat0), k0 + jnp.arange(num_iters)
        )
        self._replica = xhat_f
        self._rounds_done = k0 + num_iters
        self._record(x, num_iters, np.asarray(sent) > 0, start=k0)
        return final, (traces if trace_fn is not None else None)

    def _run_sharded(
        self, rule, x, aux, gamma, num_iters, trace_fn, state_spec, aux_spec
    ):
        """Replica-tracking rounds under shard_map: each shard carries
        its own x̂ plus one replica per in-edge permutation; only the
        encoded q crosses the ICI."""
        if trace_fn is not None:
            raise NotImplementedError(
                "per-round traces are a simulated-path (DenseMixer) feature"
            )
        pp = self._pp
        if pp.mesh is None:
            raise ValueError(
                "CompressedMixer.run over ppermute needs a mesh; build "
                "the base via PpermuteMixer.for_mesh(...)"
            )
        spec = self.spec
        base = self.base
        faulty = isinstance(base, FaultyMixer)
        sspec = self.node_pspec() if state_spec is None else state_spec
        aspec = self.node_pspec() if aux_spec is None else aux_spec
        # sent flags leave the program as a (num_iters, V) array so the
        # host can do exact per-round accounting
        sent_spec = P(None, pp.spec.axes if len(pp.spec.axes) > 1
                      else pp.spec.axes[0])
        key = (
            rule, num_iters, sspec, aspec, aux is None, spec,
            base._keep.shape if faulty else None,
        )
        fn = self._programs.get(key)
        if fn is None:
            R = base.num_rounds if faulty else 1

            def scanned(b, h0, o, keep_all, k0, g):
                my = gossip.global_node_index(pp.spec, pp.axis_sizes)

                def round_fn(carry, k):
                    x_, xhat = carry
                    refresh = self._refresh_flag(k)
                    node_key = jax.random.fold_in(self._round_key(k), my)
                    q, sent = self._node_broadcast(
                        x_, xhat, refresh, k, node_key
                    )
                    xhat2 = self._advance_replicas(xhat, q, refresh)
                    if faulty:
                        keep = keep_all[jnp.mod(k, R), :, my]
                        lap = gossip.masked_neighbor_laplacian(
                            xhat2, pp.spec, pp.axis_sizes, keep
                        )
                    else:
                        lap = gossip.neighbor_laplacian(
                            xhat2, pp.spec, pp.axis_sizes
                        )
                    lap = jax.tree.map(
                        lambda v, dl: dl.astype(v.dtype), x_, lap
                    )
                    nxt = rule(x_, lap, o, g)
                    return (nxt, xhat2), sent

                (final, xhat_f), sent = lax.scan(
                    round_fn, (b, h0), k0 + jnp.arange(num_iters)
                )
                return final, xhat_f, sent[:, None]

            if aux is None:
                fn = jax.jit(compat.shard_map(
                    lambda b, h0, keep_all, k0, g: scanned(
                        b, h0, None, keep_all, k0, g
                    ),
                    pp.mesh,
                    in_specs=(sspec, sspec, P(), P(), P()),
                    out_specs=(sspec, sspec, sent_spec),
                ))
            else:
                fn = jax.jit(compat.shard_map(
                    scanned,
                    pp.mesh,
                    in_specs=(sspec, sspec, aspec, P(), P(), P()),
                    out_specs=(sspec, sspec, sent_spec),
                ))
            self._programs[key] = fn
        gamma = jnp.asarray(gamma)
        keep_all = base._keep if faulty else jnp.zeros((1, 1, 1))
        xhat0, k0 = self._initial_replicas(x)
        k0_arr = jnp.asarray(k0)
        if aux is None:
            final, xhat_f, sent = fn(x, xhat0, keep_all, k0_arr, gamma)
        else:
            final, xhat_f, sent = fn(x, xhat0, aux, keep_all, k0_arr, gamma)
        self._replica = xhat_f
        self._rounds_done = k0 + num_iters
        self._record(x, num_iters, np.asarray(sent) > 0, start=k0)
        return final, None
