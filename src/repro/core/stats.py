"""The statistics plane: one producer of (P, Q, ||T||^2, Omega) for
every DC-ELM execution path.

Algorithm 1 steps 1-3 — h(x), P_i = H_i^T H_i, Q_i = H_i^T T_i,
Omega_i = (I/(VC) + P_i)^{-1} — used to be re-derived ad hoc at every
entry point (dc_elm.init_node, online.init_state, elm.solve_from_stats,
both elm_head layers), each with its own dtype policy and its own
explicit LU-based inverse. This module is now the single implementation:

* **Fused production.** ``SufficientStats.accumulate`` /
  ``from_raw`` stream raw (X, T) through the fused Pallas kernel
  (kernels/elm_stats.py) on TPU — the (N, L) hidden matrix is never
  materialized in HBM — or through the jitted lax.scan equivalent on
  CPU/GPU. Feature maps that cannot be fused (frozen deep backbones)
  fall back to per-chunk materialization via the gram kernels.

* **Chunked accumulation.** Stats are additive across any split of N
  (and across nodes), so ``zero -> accumulate* -> finalize`` handles
  N_i far beyond device memory. With a chunk size equal to the
  kernel's block_n the chunked stream is *bitwise* identical to the
  one-shot call (same f32 accumulation order; pinned in
  tests/test_stats.py).

* **Factorized solves.** ``finalize``/``omega_from_moments`` produce
  Omega via Cholesky (`cho_factor`/`cho_solve` on the SPD ridge Gram)
  — no dense-inverse call anywhere in src/ — and
  ``ridge_solve_moments``/``spd_solve`` are the shared beta solves for
  every ridge system (centralized, fusion-center, per-node).

Dtype policy: moments accumulate in f32 unless the inputs are f64 (the
fidelity experiments run x64 for the paper's stiff C = 2^8..2^14
solves); operands below f32 (bf16 inputs) still accumulate in f32.
Every f32 contraction on the DC-ELM path (moments, Omega and Woodbury
products, gossip rounds, predictions) passes ``precision="highest"``:
a TPU's default contracts f32 operands in one bf16 pass, which put
every check of the main path near 1e-3 relative error on a TPU v5e.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.scipy.linalg import cho_factor, cho_solve

from repro.core.features import RandomFeatureMap, RBFFeatureMap
from repro.core.scopes import phase


def accum_dtype(*operands) -> jnp.dtype:
    """f32 accumulation, upgraded to f64 only by f64 inputs."""
    dt = jnp.result_type(*operands)
    return jnp.dtype(jnp.float64) if dt == jnp.float64 else jnp.dtype(
        jnp.float32
    )


def fusable_params(feature_map):
    """(W, b, activation) for the fused kernel, or None.

    RandomFeatureMap -> (weights, bias, activation); RBFFeatureMap ->
    (centers^T, gamma, "rbf"). Anything else (deep-backbone adapters)
    is not an affine/RBF map and takes the materialize-per-chunk path.
    """
    if isinstance(feature_map, RandomFeatureMap):
        return feature_map.weights, feature_map.bias, feature_map.activation
    if isinstance(feature_map, RBFFeatureMap):
        return feature_map.centers.T, feature_map.gamma, "rbf"
    return None


# ---------------------------------------------------------------------------
# Moment production
# ---------------------------------------------------------------------------


def hidden_moments(H: jax.Array, T: jax.Array, *, dtype=None):
    """(P, Q) = (H^T H, H^T T) from a materialized H, f32/f64 acc.

    The gram contraction keeps H's operand dtype (bf16 operands feed
    the MXU) with `preferred_element_type` accumulation; the cross
    moment promotes its operands to the wider of H/T so f32 targets are
    never quantized down to a bf16 feature dtype.
    """
    dtype = accum_dtype(H, T) if dtype is None else dtype
    P = jax.lax.dot_general(
        H, H, dimension_numbers=(((0,), (0,)), ((), ())),
        precision="highest", preferred_element_type=dtype,
    )
    op = jnp.promote_types(H.dtype, T.dtype)
    Q = jax.lax.dot_general(
        H.astype(op), T.astype(op),
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision="highest", preferred_element_type=dtype,
    )
    return P, Q


def raw_moments(
    X: jax.Array, T: jax.Array, feature_map, *,
    use_kernel: bool | None = None, dtype=None, **kw,
):
    """(P, Q) from raw inputs; fused (H never materialized) when the
    feature map is affine/RBF and the accumulator is f32."""
    dtype = accum_dtype(X, T) if dtype is None else jnp.dtype(dtype)
    params = fusable_params(feature_map)
    with phase("stats"):
        if params is not None and dtype == jnp.float32:
            from repro.kernels import elm_stats_ops

            W, b, activation = params
            return elm_stats_ops.fused_moments(
                X, W, b, T, activation=activation, use_kernel=use_kernel,
                **kw,
            )
        # non-fusable feature map (deep backbone) or f64 fidelity path:
        # materialize H for this call only — callers chunk N
        return hidden_moments(feature_map(X), T, dtype=dtype)


# ---------------------------------------------------------------------------
# SufficientStats
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SufficientStats:
    """One node's additive ELM statistics.

    P:     (L, L) moment H^T H
    Q:     (L, M) cross moment H^T T
    t_sq:  ()     ||T||^2 (closes the expanded quadratic, paper eq. 18)
    count: ()     samples seen
    """

    P: jax.Array
    Q: jax.Array
    t_sq: jax.Array
    count: jax.Array

    @classmethod
    def zero(cls, L: int, M: int, dtype=jnp.float32) -> "SufficientStats":
        return cls(
            P=jnp.zeros((L, L), dtype),
            Q=jnp.zeros((L, M), dtype),
            t_sq=jnp.zeros((), dtype),
            count=jnp.zeros((), dtype),
        )

    @property
    def num_features(self) -> int:
        return self.P.shape[-1]

    @property
    def num_targets(self) -> int:
        return self.Q.shape[-1]

    def accumulate(
        self, X_chunk: jax.Array, T_chunk: jax.Array, feature_map, *,
        use_kernel: bool | None = None, **kw,
    ) -> "SufficientStats":
        """Fold one raw (X, T) chunk in — the streaming entry point."""
        dP, dQ = raw_moments(
            X_chunk, T_chunk, feature_map,
            use_kernel=use_kernel, dtype=self.P.dtype, **kw,
        )
        return self._add(dP, dQ, T_chunk)

    def accumulate_hidden(
        self, H_chunk: jax.Array, T_chunk: jax.Array
    ) -> "SufficientStats":
        """Fold a chunk whose features are already materialized."""
        dP, dQ = hidden_moments(H_chunk, T_chunk, dtype=self.P.dtype)
        return self._add(dP, dQ, T_chunk)

    def _add(self, dP, dQ, T_chunk) -> "SufficientStats":
        dt = self.P.dtype
        Tf = T_chunk.astype(dt)
        return SufficientStats(
            P=self.P + dP.astype(dt),
            Q=self.Q + dQ.astype(dt),
            t_sq=self.t_sq + jnp.sum(Tf * Tf),
            count=self.count + jnp.asarray(T_chunk.shape[0], dt),
        )

    def merge(self, other: "SufficientStats") -> "SufficientStats":
        """Additive fusion (across chunks or across nodes)."""
        return SufficientStats(
            P=self.P + other.P, Q=self.Q + other.Q,
            t_sq=self.t_sq + other.t_sq, count=self.count + other.count,
        )

    def finalize(self, C: float, V: int = 1):
        """(Omega, beta0): the paper's eq. 21 node init, via Cholesky.

        Omega = (I/(VC) + P)^{-1}, beta0 = Omega Q. beta0 is computed
        as Omega @ Q (not a second solve) so it equals the streaming
        re-seed ``online.reseed_betas`` bit-for-bit.
        """
        omega = omega_from_moments(self.P, C, V)
        return omega, jnp.matmul(omega, self.Q, precision="highest")


def from_hidden(H: jax.Array, T: jax.Array, *, dtype=None) -> SufficientStats:
    """One-shot stats from a materialized H (the legacy entry shape)."""
    dtype = accum_dtype(H, T) if dtype is None else jnp.dtype(dtype)
    L, M = H.shape[-1], T.shape[-1]
    return SufficientStats.zero(L, M, dtype).accumulate_hidden(H, T)


def from_raw(
    X: jax.Array, T: jax.Array, feature_map, *,
    chunk: int | None = None, use_kernel: bool | None = None,
    dtype=None, **kw,
) -> SufficientStats:
    """Stats from raw inputs; H is never materialized on fusable maps.

    chunk: split N into chunks of this many rows (the kernel already
    streams N internally, so chunking matters when X itself exceeds
    device memory or the feature map is non-fusable).
    """
    dtype = accum_dtype(X, T) if dtype is None else jnp.dtype(dtype)
    L = feature_map.num_features
    M = T.shape[-1]
    s = SufficientStats.zero(L, M, dtype)
    if chunk is None:
        return s.accumulate(X, T, feature_map, use_kernel=use_kernel, **kw)
    N = X.shape[0]
    for start in range(0, N, chunk):
        s = s.accumulate(
            X[start:start + chunk], T[start:start + chunk], feature_map,
            use_kernel=use_kernel, **kw,
        )
    return s


def classification_moments(
    H: jax.Array, labels: jax.Array, num_classes: int, *,
    mask: jax.Array | None = None, use_kernel: bool | None = None,
) -> SufficientStats:
    """Stats for one-hot targets without materializing the one-hot.

    P via the gram kernel on the (masked) features, Q = H^T onehot via
    segment-sum, ||T||^2 = number of valid labels. mask: bool (N,)
    marking rows that count (invalid rows are zeroed out of H).
    """
    from repro.kernels import gram_ops

    if mask is None:
        mask = labels >= 0
    Hm = jnp.where(mask[:, None], H, 0.0).astype(H.dtype)
    P = gram_ops.gram(Hm, use_kernel=use_kernel)
    Q = jax.ops.segment_sum(
        Hm.astype(jnp.float32), jnp.maximum(labels, 0),
        num_segments=num_classes,
    ).T
    n = jnp.sum(mask.astype(jnp.float32))
    return SufficientStats(
        P=P, Q=Q, t_sq=n, count=n,  # ||onehot||^2 == valid-row count
    )


# ---------------------------------------------------------------------------
# Factorized solves — the only Omega/beta producers in src/
# ---------------------------------------------------------------------------


def spd_solve(A: jax.Array, B: jax.Array) -> jax.Array:
    """Solve A X = B for symmetric positive-definite A via Cholesky."""
    return cho_solve(cho_factor(A), B)


def omega_from_moments(P: jax.Array, C: float, V: int = 1) -> jax.Array:
    """Omega = (I/(VC) + P)^{-1} — THE preconditioner producer.

    The ridge Gram is SPD by construction, so the Cholesky factor
    always exists; cho_solve against I beats an LU-based inverse on
    both flops and accuracy for the paper's stiff C values.
    """
    L = P.shape[-1]
    with phase("omega"):
        eye = jnp.eye(L, dtype=P.dtype)
        return spd_solve(eye / (V * C) + P, eye)


def finalize_moments(P: jax.Array, Q: jax.Array, C: float, V: int = 1):
    """(Omega, beta0) from bare moments (paper eq. 21)."""
    omega = omega_from_moments(P, C, V)
    return omega, jnp.matmul(omega, Q, precision="highest")


def ridge_solve_moments(P: jax.Array, Q: jax.Array, C: float) -> jax.Array:
    """beta = (I/C + P)^{-1} Q via Cholesky — when Omega itself is not
    needed (centralized / fusion-center solves)."""
    L = P.shape[-1]
    return spd_solve(jnp.eye(L, dtype=P.dtype) / C + P, Q)
