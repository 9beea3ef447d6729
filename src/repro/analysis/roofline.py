"""Three-term roofline from a compiled dry-run artifact.

  compute    = HLO_FLOPs / (chips * PEAK_FLOPS)
  memory     = HLO_bytes / (chips * HBM_BW)
  collective = wire_bytes / (chips * ICI_BW)

Hardware constants (TPU v5e-like, per assignment): 197 TFLOP/s bf16,
819 GB/s HBM, ~50 GB/s/link ICI.

FLOPs / HBM bytes / collective bytes come from the trip-count-weighted
HLO analyzer in analysis/hlo.py — ``compiled.cost_analysis()`` counts
while-loop (lax.scan) bodies once, so a scanned 80-layer stack would be
undercounted ~80x; the raw cost_analysis numbers are still recorded for
reference. All quantities are per-chip (the partitioned module is the
per-device program). Collective wire bytes apply per-kind ring factors
to result-shape sums.
"""

from __future__ import annotations

import dataclasses

from repro.analysis.hlo import CollectiveStats, ModuleStats, analyze_module

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s / link (conservative single-link figure)

# ring-algorithm wire factors applied to result-shape bytes
_WIRE_FACTOR = {
    "all-gather": 1.0,  # each device receives ~result bytes
    "all-reduce": 2.0,  # reduce-scatter + all-gather phases
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,  # one neighbor hop, send == recv
}


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    flops_per_chip: float
    hbm_bytes_per_chip: float
    wire_bytes_per_chip: float
    collectives: CollectiveStats
    peak_memory_per_chip: float  # from memory_analysis
    model_flops: float  # 6*N(active)*D analytic
    chips: int
    raw_cost_analysis: dict | None = None  # XLA's (scan-undercounted) view

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_chip / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_per_chip / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / total HLO FLOPs (all chips)."""
        total = self.flops_per_chip * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def step_time_estimate(self) -> float:
        """Roofline lower bound (no overlap assumed across terms)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_chip": self.flops_per_chip,
            "hbm_bytes_per_chip": self.hbm_bytes_per_chip,
            "wire_bytes_per_chip": self.wire_bytes_per_chip,
            "collective_bytes_by_kind": self.collectives.bytes_by_kind,
            "collective_count_by_kind": self.collectives.count_by_kind,
            "peak_memory_per_chip": self.peak_memory_per_chip,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "step_time_estimate_s": self.step_time_estimate,
            "raw_cost_analysis": self.raw_cost_analysis,
        }


# ---------------------------------------------------------------------------
# Gossip-round time model (the consensus plane's eq. (20) hot loop)
# ---------------------------------------------------------------------------


def gossip_round_terms(
    V: int, d_max: int, L: int, M: int, *, itemsize: int = 4,
    dense: bool = False,
) -> dict:
    """Roofline terms for one eq. (20) consensus round.

    Per round every node forms lap_i = sum_j a_ij (beta_j - beta_i)
    over ``d_max`` neighbors (``V`` fan-in on the ``dense=True``
    matmul formulation) and contracts it against Omega_i — the
    ``2*V*L*L*M`` Omega FLOPs both formulations share. HBM traffic is
    the state in+out, the Omegas, and the neighbor lists (or the dense
    adjacency); ``gather_bytes`` is the neighbor-gather volume the
    fused kernel keeps in VMEM (reported separately — it only hits HBM
    when an unfused path materializes the gathered tiles).

    Used for relative ranking (the dense-vs-neighbor arm choice in
    ``kernels/elm_gossip_ops.py``) — the absolute constants cancel out
    of that comparison.
    """
    fanin = V if dense else d_max
    flops = 2.0 * V * fanin * L * M + 2.0 * V * L * L * M
    state = itemsize * (2.0 * V * L * M + V * L * L)
    lists = itemsize * V * V if dense else 2.0 * itemsize * V * d_max
    gather_bytes = itemsize * V * fanin * L * M
    t_compute = flops / PEAK_FLOPS
    t_memory = (state + lists) / HBM_BW
    return {
        "flops": flops,
        "hbm_bytes": state + lists,
        "gather_bytes": gather_bytes,
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_round": max(t_compute, t_memory),
    }


def model_flops_estimate(cfg, shape) -> float:
    """6 * N_active * D for training; 2 * N_active * D_tokens for decode."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def roofline_from_compiled(
    compiled,
    *,
    cfg,
    shape,
    mesh_name: str,
    chips: int,
) -> RooflineTerms:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older API returns [dict]
        cost = cost[0]
    raw = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
    }
    text = compiled.as_text()
    mod: ModuleStats = analyze_module(text)
    flops = mod.flops
    hbm = mod.hbm_bytes
    colls = CollectiveStats(
        bytes_by_kind=mod.bytes_by_kind, count_by_kind=mod.count_by_kind
    )
    wire = 0.0
    for kind, b in colls.bytes_by_kind.items():
        wire += _WIRE_FACTOR[kind] * b
    mem = compiled.memory_analysis()
    peak = float(
        getattr(mem, "temp_size_in_bytes", 0)
        + getattr(mem, "argument_size_in_bytes", 0)
        + getattr(mem, "output_size_in_bytes", 0)
        - getattr(mem, "alias_size_in_bytes", 0)
    )
    return RooflineTerms(
        arch=cfg.name,
        shape=shape.name,
        mesh=mesh_name,
        flops_per_chip=flops,
        hbm_bytes_per_chip=hbm,
        wire_bytes_per_chip=wire,
        collectives=colls,
        peak_memory_per_chip=peak,
        model_flops=model_flops_estimate(cfg, shape),
        chips=chips,
    )
