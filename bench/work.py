"""Operations and bytes that the algorithm needs, counted from shapes.

They count the work itself, whatever implements it: the symmetric P
counts once, as N L (L + 1); recomputed hidden tiles, padding and the
zero entries of a dense adjacency do not count. Bytes are float32 (4
bytes) reads and writes of HBM that no implementation can avoid.
"""

from __future__ import annotations

F32 = 4


def stats_terms(N: int, D: int, L: int, M: int) -> tuple[float, float]:
    """One node's (P, Q) = (H^T H, H^T T) with H = g(X W + b), fused."""
    flops = 2.0 * N * D * L + N * L * (L + 1) + 2.0 * N * L * M
    nbytes = F32 * (N * D + D * L + L + N * M + L * L + L * M)
    return flops, nbytes


def omega_flops(L: int, M: int) -> float:
    """Omega = (I/(VC) + P)^-1 by Cholesky (L^3 / 3) and two triangular
    solves against I (2 L^3), then the seed beta = Omega Q (2 L^2 M)."""
    return L**3 / 3.0 + 2.0 * L**3 + 2.0 * L * L * M


def round_flops(V: int, edges: int, L: int, M: int) -> float:
    """One eq. (20) round: the Laplacian over the ``edges`` directed
    edges, then Omega_i times it on every node."""
    return 2.0 * edges * L * M + 2.0 * V * L * L * M


def gossip_round_terms(
    V: int, d_max: int, L: int, M: int, *, itemsize: int = F32,
    dense: bool = False,
) -> dict:
    """FLOPs and HBM bytes of one eq. (20) round over padded neighbor
    lists of width ``d_max`` (the program's own gossip-round model):
    state in and out, every Omega_i, and the neighbor lists."""
    fanin = V if dense else d_max
    flops = 2.0 * V * fanin * L * M + 2.0 * V * L * L * M
    state = itemsize * (2.0 * V * L * M + V * L * L)
    lists = itemsize * V * V if dense else 2.0 * itemsize * V * d_max
    return {"flops": flops, "hbm_bytes": state + lists}


def woodbury_flops(L: int, M: int, dN: int) -> float:
    """One node's rank-dN Woodbury add: U = Omega dH^T (2 L^2 dN), the
    dN x dN capacitance I + dH U (2 dN^2 L) and its solve (dN^3),
    Omega -= U S^-1 U^T (2 L dN^2 + 2 L^2 dN), Q += dH^T dT (2 dN L M),
    and the re-seed beta = Omega Q (2 L^2 M)."""
    return (
        4.0 * L * L * dN + 4.0 * dN * dN * L + dN**3
        + 2.0 * dN * L * M + 2.0 * L * L * M
    )


def predict_terms(rows: int, launches: int, D: int, L: int, M: int):
    """Served rows g(x W + b) beta: FLOPs over the rows asked for, bytes
    of the rows in and out plus W, b and one beta per launch."""
    flops = rows * (2.0 * D * L + 2.0 * L * M)
    nbytes = F32 * (rows * (D + M) + launches * (D * L + L + L * M))
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time at the bf16 peak and
    memory time at the HBM peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
