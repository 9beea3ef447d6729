"""Event-driven asynchronous gossip runtime (no global round barrier).

Every mixer in core/mixers.py advances the network in lockstep rounds:
one straggler stalls all V nodes, and a dropped message must be dropped
symmetrically to keep the Laplacian well-posed. This module removes the
barrier. Each node lives on its own clock: when its local event fires
it (1) absorbs whatever messages have arrived in its per-peer inboxes,
(2) applies its update rule, (3) pushes messages to its out-neighbors,
each independently subject to the message-loss process (a
``consensus.FaultModel`` trace, indexed by *send time* instead of round
number) and the per-edge latency distribution
(``consensus.DelayModel``). Nothing anywhere waits for anything.

Two update rules:

* ``PushSumRule`` — ratio consensus over the moment masses
  (core/push_sum.py). Converges to the *centralized* beta* under
  drops, delays, reordering, and arbitrary relative timing; this is
  the default and the point of the subsystem.
* ``LaplacianRule`` — the paper's eq. (20) applied to the messages at
  hand. Under the barrier schedule (unit fire periods, zero delay) it
  replays ``FaultyMixer(DenseMixer)`` *exactly* — message present iff
  the round mask kept the edge — which is what pins the synchronous
  engines as the zero-delay/zero-loss special case of this runtime.

Everything runs on a deterministic virtual clock: events live in a
heap keyed (time, seq), all randomness (drop draws via the fault
trace, delay jitter) comes from one seeded generator, and the engine
records an event log — so the same seed replays the same run
bit-for-bit (the nightly seed-sweep stress job asserts exactly this,
plus the push-sum conservation law, across >= 20 seeds). This is the
injectable-clock idiom of ``serving.ContinuousELMServer`` applied to
the training plane.

``AsyncEngine.run_until(residual_tol=..., t_max=...)`` is the drop-in
alternative to ``ConsensusEngine.run``: instead of "mix K rounds" you
say "gossip until the network disagrees by less than tol (or virtual
time runs out)". Wire traffic is billed through the exact
``compression.WireStats`` accounting every synchronous mixer uses.

See DESIGN.md §13 and the README async quickstart.
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import Any

import numpy as np

from repro.core import push_sum
from repro.core.consensus import DelayModel, FaultModel, Graph

# event kinds, ordered within a timestamp by scheduling seq
_FIRE = "fire"
_DELIVER = "deliver"


# ---------------------------------------------------------------------------
# Update rules
# ---------------------------------------------------------------------------


class PushSumRule:
    """Robust ratio consensus over the DC-ELM moment masses.

    Gossips cumulative (running-sum) mass counters of the pair
    (A_i = I/(VC) + P_i, Q_i) plus the scalar rho; each node's
    estimate solve(sigma_A, sigma_Q) converges to the centralized
    beta* on any jointly-connected directed/lossy/async sequence.
    State, counters, and the conservation law live in
    core/push_sum.py.
    """

    def __init__(self, graph: Graph, P, Q, C: float):
        self.graph = graph
        self.C = float(C)
        self.sigmas = push_sum.init_masses(P, Q, C)
        self.total0 = push_sum.total_mass(self.sigmas)
        V = graph.num_nodes
        self.out_neighbors = [
            [int(j) for j in graph.neighbors(i)] for i in range(V)
        ]
        L, M = self.sigmas[0].A.shape[0], self.sigmas[0].Q.shape[1]
        self._shape = (L, M)
        # cumulative counters: mu = mass ever *sent* on (i, j),
        # nu = mass ever *processed* from (i, j); a message carries a
        # snapshot of mu, so any delivery catches the receiver up past
        # every drop before it
        self.mu = {
            (i, j): push_sum.Mass.zeros(L, M)
            for i in range(V)
            for j in self.out_neighbors[i]
        }
        self.nu = {k: push_sum.Mass.zeros(L, M) for k in self.mu}
        self._last_seq = dict.fromkeys(self.mu, -1)

    @property
    def num_nodes(self) -> int:
        return self.graph.num_nodes

    def payload_floats(self) -> int:
        """Floats per message: the cumulative (A, Q, rho) counter."""
        L, M = self._shape
        return L * L + L * M + 1

    def fire(self, i: int, inbox: dict) -> dict:
        """One local event: absorb counters, split mass, emit counters.

        inbox: {sender j: (seq, Mass cumulative)} — newest per sender.
        Returns {out-neighbor j: (payload to put on the wire)}.
        """
        for j, (seq, latest) in inbox.items():
            key = (j, i)
            if seq <= self._last_seq[key]:
                continue  # stale reordering: newer counter already in
            self.sigmas[i].add_diff(latest, self.nu[key])
            self.nu[key] = latest.copy()
            self._last_seq[key] = seq
        out = self.out_neighbors[i]
        w = push_sum.split_share(len(out))
        sends = {}
        for j in out:
            self.mu[(i, j)].add_scaled(self.sigmas[i], w)
            sends[j] = self.mu[(i, j)].copy()
        self.sigmas[i].scale(w)
        return sends

    def estimate(self, i: int) -> np.ndarray:
        return push_sum.estimate(self.sigmas[i])

    def betas(self) -> np.ndarray:
        return np.stack([self.estimate(i) for i in range(self.num_nodes)])

    def conservation_residual(self) -> float:
        """Relative violation of the mass-conservation invariant —
        roundoff-sized at *every* instant, by construction."""
        return push_sum.conservation_residual(
            self.sigmas, self.mu, self.nu, self.total0
        )


class LaplacianRule:
    """Paper eq. (20) on whatever messages have arrived.

    lap_i = sum over senders j of a_ij (beta_j^msg - beta_i), i.e. a
    neighbor contributes this fire iff a message from it survived the
    wire since the last fire (newest wins). Under the barrier schedule
    this is *exactly* the ``FaultyMixer(DenseMixer)`` masked Laplacian;
    under genuinely async timing it has no exactness guarantee (stale
    betas bias the fixed point) — use ``PushSumRule`` there. Static
    adjacency only (the sync engines' time-varying snapshots have no
    canonical async analogue).
    """

    def __init__(self, graph: Graph, betas, omegas, gamma: float, C: float,
                 *, dtype=np.float64):
        self.graph = graph
        self.gamma = float(gamma)
        self.C = float(C)
        self._betas = np.array(betas, dtype=dtype)
        self._omegas = np.array(omegas, dtype=dtype)
        self._adj = np.asarray(graph.adjacency, dtype=dtype)
        V = graph.num_nodes
        self.out_neighbors = [
            [int(j) for j in graph.neighbors(i)] for i in range(V)
        ]
        self._last_seq = {
            (i, j): -1 for i in range(V) for j in self.out_neighbors[i]
        }

    @property
    def num_nodes(self) -> int:
        return self.graph.num_nodes

    def payload_floats(self) -> int:
        L, M = self._betas.shape[1], self._betas.shape[2]
        return L * M

    def fire(self, i: int, inbox: dict) -> dict:
        lap = np.zeros_like(self._betas[i])
        for j, (seq, beta_j) in inbox.items():
            if seq <= self._last_seq[(j, i)]:
                continue
            self._last_seq[(j, i)] = seq
            lap += self._adj[i, j] * (beta_j - self._betas[i])
        V, C = self.num_nodes, self.C
        self._betas[i] = self._betas[i] + (
            self.gamma / (V * C)
        ) * (self._omegas[i] @ lap)
        payload = self._betas[i].copy()
        return {j: payload for j in self.out_neighbors[i]}

    def estimate(self, i: int) -> np.ndarray:
        return self._betas[i]

    def betas(self) -> np.ndarray:
        return self._betas.copy()


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AsyncResult:
    """Outcome of one ``run_until`` leg (the engine keeps running state;
    successive calls continue the same virtual timeline)."""

    betas: np.ndarray  # (V, L, M) node estimates at stop time
    t: float  # virtual time at stop
    fires: int  # local events processed (this leg)
    sends: int  # messages put on a live link (this leg)
    drops: int  # of those, lost to the fault trace (this leg)
    residual: float  # last residual measured
    converged: bool  # residual <= residual_tol at stop


class AsyncEngine:
    """Deterministic virtual-clock scheduler driving an update rule.

    graph: the communication topology (message routes).
    rule: ``PushSumRule`` (default choice) or ``LaplacianRule``.
    faults: optional ``consensus.FaultModel`` whose ``edge_keep`` trace
        becomes the per-message drop process — the mask row is indexed
        by floor(send time) % fault_rounds, so the barrier schedule
        replays mask k at round k exactly like ``FaultyMixer``, and a
        certified trace stays certified here.
    delays: optional ``consensus.DelayModel``; None = zero latency
        (messages arrive at the send instant, consumed at the
        receiver's next fire — the synchronous limit).
    fire_periods: per-node firing periods (virtual-time units between
        local events), default all 1.0. A straggling node is a large
        entry here; nobody else slows down.
    seed: one generator for delay jitter (drop draws are already
        deterministic inside the FaultModel trace). Same seed + same
        config => identical event log, asserted nightly.

    Events are (time, seq)-ordered: seq is the scheduling order, so
    same-instant events process in the order they were created — fires
    scheduled last round before deliveries sent this instant — which is
    what makes the zero-delay limit well-defined instead of racy.
    """

    def __init__(
        self,
        graph: Graph,
        rule: Any,
        *,
        faults: FaultModel | None = None,
        delays: DelayModel | None = None,
        fire_periods=None,
        fault_rounds: int = 1024,
        seed: int = 0,
        log_events: bool = True,
    ):
        V = graph.num_nodes
        if rule.num_nodes != V:
            raise ValueError(
                f"rule is sized for {rule.num_nodes} nodes, graph has {V}"
            )
        if faults is not None and faults.num_nodes != V:
            raise ValueError(
                f"fault model is over {faults.num_nodes} nodes, graph has {V}"
            )
        self.graph = graph
        self.rule = rule
        self.delays = delays
        self._keep = (
            None if faults is None else faults.edge_keep(int(fault_rounds))
        )
        periods = (
            np.ones(V) if fire_periods is None
            else np.asarray(fire_periods, dtype=np.float64)
        )
        if periods.shape != (V,) or np.any(periods <= 0):
            raise ValueError(
                f"fire_periods must be (V,) positive, got {periods!r}"
            )
        self.fire_periods = periods
        self.rng = np.random.default_rng(seed)
        self.log_events = bool(log_events)
        self.event_log: list[tuple] = []
        self.t = 0.0
        self.last_wire_stats = None
        self.total_bytes_on_wire = 0
        self._heap: list[tuple] = []
        self._seq = 0
        self._send_seq = dict.fromkeys(
            ((i, j) for i in range(V) for j in graph.neighbors(i)), -1
        )
        self._inbox: list[dict] = [{} for _ in range(V)]
        self._fires_total = 0
        # every node's first local event is at t = 0 (node order seeds
        # the seq tie-break, so the barrier schedule is deterministic)
        for i in range(V):
            self._push(0.0, _FIRE, i)

    # ------------------------------------------------------------- internals

    def _push(self, t: float, kind: str, *payload) -> None:
        heapq.heappush(self._heap, (t, self._seq, kind, payload))
        self._seq += 1

    def _dropped(self, i: int, j: int, t_send: float) -> bool:
        if self._keep is None:
            return False
        R = self._keep.shape[0]
        return self._keep[int(np.floor(t_send)) % R, i, j] == 0.0

    def _delay(self, i: int, j: int) -> float:
        if self.delays is None:
            return 0.0
        return self.delays.sample(self.rng, i, j)

    def _log(self, *rec) -> None:
        if self.log_events:
            self.event_log.append(rec)

    def _process_fire(self, t: float, i: int) -> tuple[int, int]:
        """Run node i's local event; returns (#sends, #drops)."""
        inbox, self._inbox[i] = self._inbox[i], {}
        sends = self.rule.fire(i, inbox)
        self._log(_FIRE, t, i)
        n_sent = n_drop = 0
        for j, payload in sends.items():
            seq = self._send_seq[(i, j)] = self._send_seq[(i, j)] + 1
            n_sent += 1
            if self._dropped(i, j, t):
                n_drop += 1
                self._log("drop", t, i, j, seq)
                continue
            self._push(t + self._delay(i, j), _DELIVER, i, j, seq, payload)
            self._log("send", t, i, j, seq)
        self._push(t + self.fire_periods[i], _FIRE, i)
        self._fires_total += 1
        return n_sent, n_drop

    def _process_deliver(self, t, i, j, seq, payload) -> None:
        """Message from i lands in j's inbox (newest per sender wins —
        the rule's seq guard makes stale reorderings no-ops anyway)."""
        have = self._inbox[j].get(i)
        if have is None or seq > have[0]:
            self._inbox[j][i] = (seq, payload)
        self._log(_DELIVER, t, i, j, seq)

    def _residual(self, target) -> float:
        betas = self.rule.betas()
        if target is None:
            ref = betas.mean(axis=0)
        else:
            ref = np.asarray(target)
        num = np.sqrt(((betas - ref[None]) ** 2).sum(axis=(1, 2))).max()
        den = 1.0 + float(np.sqrt((ref**2).sum()))
        return float(num) / den

    def _record_wire(self, fires, sends, drops, per_fire_bytes) -> None:
        from repro.core import compression

        floats = self.rule.payload_floats()
        msg_bytes = floats * 8  # the runtime's masses are float64
        stats = compression.WireStats(
            rounds=fires,
            links_live=sends,
            links_sent=sends - drops,
            bytes_on_wire=(sends - drops) * msg_bytes,
            bytes_uncompressed=sends * msg_bytes,
            per_round_bytes=np.asarray(per_fire_bytes, dtype=np.int64),
        )
        compression.record_wire_stats(self, stats)
        self.total_bytes_on_wire += stats.bytes_on_wire

    # ------------------------------------------------------------------ api

    @property
    def wire_stats(self):
        """``compression.WireStats`` of the last ``run_until`` leg: one
        "round" = one fire event, a live link = an attempted send, a
        sent link = a send the fault trace did not eat."""
        return self.last_wire_stats

    def betas(self) -> np.ndarray:
        """(V, L, M) current per-node estimates."""
        return self.rule.betas()

    def run_until(
        self,
        *,
        residual_tol: float | None = None,
        t_max: float | None = None,
        target=None,
        check_every: int | None = None,
    ) -> AsyncResult:
        """Drive events until the residual is below tol or the virtual
        clock passes t_max (drop-in for ``ConsensusEngine.run``'s
        "K rounds": say how converged instead of how many).

        residual_tol: stop when max_i ||beta_i - ref|| / (1 + ||ref||)
            <= tol, with ref = the node mean (consensus residual) or
            ``target`` (e.g. the centralized beta*) when given.
        t_max: stop when the next event would pass this virtual time
            (measured from t=0 of the engine's life, not of this call).
        check_every: fires between residual evaluations (default V —
            once per average network sweep); the estimate solve is the
            expensive part of a push-sum step, so it is not done per
            event.

        Returns an ``AsyncResult``; the engine stays live, so a later
        ``run_until`` continues the same timeline (liveness probes,
        straggler sweeps, "gossip a bit more" flows).
        """
        if residual_tol is None and t_max is None:
            raise ValueError("need residual_tol and/or t_max")
        V = self.graph.num_nodes
        check_every = V if check_every is None else int(check_every)
        fires = sends = drops = 0
        per_fire_bytes: list[int] = []
        msg_bytes = self.rule.payload_floats() * 8
        residual = np.inf
        converged = False
        since_check = 0
        while self._heap:
            t_next = self._heap[0][0]
            if t_max is not None and t_next > t_max:
                break
            t, _, kind, payload = heapq.heappop(self._heap)
            self.t = t
            if kind == _DELIVER:
                self._process_deliver(t, *payload)
                continue
            n_sent, n_drop = self._process_fire(t, payload[0])
            fires += 1
            sends += n_sent
            drops += n_drop
            per_fire_bytes.append((n_sent - n_drop) * msg_bytes)
            since_check += 1
            if residual_tol is not None and since_check >= check_every:
                since_check = 0
                residual = self._residual(target)
                if residual <= residual_tol:
                    converged = True
                    break
        if residual_tol is not None and not converged:
            residual = self._residual(target)
            converged = residual <= residual_tol
        self._record_wire(fires, sends, drops, per_fire_bytes)
        return AsyncResult(
            betas=self.rule.betas(),
            t=self.t,
            fires=fires,
            sends=sends,
            drops=drops,
            residual=float(residual) if np.isfinite(residual) else residual,
            converged=converged,
        )


# ---------------------------------------------------------------------------
# Convenience constructors
# ---------------------------------------------------------------------------


def async_dc_elm(
    graph: Graph,
    P,
    Q,
    C: float,
    **kwargs,
) -> AsyncEngine:
    """Push-sum DC-ELM over ``graph`` from per-node statistics
    P:(V,L,L), Q:(V,L,M) — the async counterpart of
    ``engine.simulated_dc_elm`` + ``run``: every node's estimate
    converges to the centralized beta* without a round barrier.
    kwargs go to ``AsyncEngine`` (faults/delays/fire_periods/seed/...).
    """
    return AsyncEngine(graph, PushSumRule(graph, P, Q, C), **kwargs)


def sync_limit_dc_elm(
    graph: Graph,
    betas,
    omegas,
    gamma: float,
    C: float,
    *,
    faults: FaultModel | None = None,
    fault_rounds: int = 1024,
    dtype=np.float64,
    **kwargs,
) -> AsyncEngine:
    """The synchronous engines as a special case of the async runtime:
    eq. (20) under the barrier schedule (unit periods, zero delay).

    ``run_until(t_max=K)`` then reproduces
    ``engine.with_faults(simulated_dc_elm(...), ...).run(...)`` for K
    rounds *exactly* (same masked Laplacian, same update, same fault
    trace — mask row k gates the messages of round k), which is the
    parity claim tests/test_async.py pins.
    """
    rule = LaplacianRule(graph, betas, omegas, gamma, C, dtype=dtype)
    return AsyncEngine(
        graph, rule, faults=faults, fault_rounds=fault_rounds,
        delays=None, fire_periods=None, **kwargs,
    )
