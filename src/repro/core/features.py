"""ELM random feature maps (the paper's hidden layer h(x)).

The ELM hidden layer is a *frozen random* map
    h(x) = [g(w_1, b_1, x), ..., g(w_L, b_L, x)],  h: R^D -> R^L
with g a nonlinear piecewise-continuous activation (paper Sec. II-A).
All nodes share the same (W, b) (paper Algorithm 1, step 1).

``ACTIVATIONS`` is the one activation registry in the codebase: the
fused feature->moment Pallas kernel (kernels/elm_stats.py) applies the
same callables inside its VMEM tiles that ``FeatureMap.__call__``
applies on materialized arrays, so the two paths cannot drift.

``FeatureMap`` is also the integration point for the "beyond paper"
deep-backbone features (paper Sec. V future work: unknown feature
mappings): models/ provides a FeatureMap whose ``__call__`` runs a
frozen transformer trunk.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.scopes import phase

Activation = Callable[[jax.Array], jax.Array]

# The shared activation registry (name -> elementwise g). "rbf" is not
# listed here because it is not an affine-then-nonlinearity map — it has
# its own FeatureMap class and kernel branch (see `rbf_squared_dists`).
ACTIVATIONS: dict[str, Activation] = {
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "sin": jnp.sin,
    "identity": lambda x: x,
}

# historical private alias (pre-stats-plane consumers imported this)
_ACTIVATIONS = ACTIVATIONS


def valid_activations() -> tuple[str, ...]:
    """All activation names accepted by make_random_features."""
    return tuple(ACTIVATIONS) + ("rbf",)


@dataclasses.dataclass(frozen=True)
class RandomFeatureMap:
    """Affine-then-nonlinearity random feature map.

    Attributes:
      weights: (D, L) input-to-hidden weights w_l (columns).
      bias: (L,) hidden biases b_l.
      activation: name of g (a key of ``ACTIVATIONS``).
    """

    weights: jax.Array
    bias: jax.Array
    activation: str = "sigmoid"

    def __post_init__(self):
        if self.activation not in ACTIVATIONS:
            raise ValueError(
                f"unknown activation {self.activation!r}; "
                f"valid: {sorted(ACTIVATIONS)} "
                "(gaussian hidden nodes are RBFFeatureMap, not a "
                "RandomFeatureMap activation)"
            )

    @property
    def in_dim(self) -> int:
        return self.weights.shape[0]

    @property
    def num_features(self) -> int:
        return self.weights.shape[1]

    def __call__(self, x: jax.Array) -> jax.Array:
        """x: (..., D) -> H: (..., L)."""
        g = ACTIVATIONS[self.activation]
        with phase("features"):
            z = jnp.matmul(x, self.weights, precision="highest")
            return g(z + self.bias)


def rbf_squared_dists(
    x: jax.Array, centers: jax.Array, centers_sq: jax.Array | None = None
) -> jax.Array:
    """||x - c||^2 for all centers via ||x||^2 - 2 x.c^T + ||c||^2.

    One (..., L) result from a single (..., D) x (D, L) matmul — never
    the (..., L, D) broadcast intermediate (an HBM blowup at large L*D).
    Clamped at zero: the expansion can go slightly negative in floating
    point when x is near a center. Shared by ``RBFFeatureMap.__call__``
    and the fused kernel's oracle (kernels/elm_stats_ref.py).
    """
    if centers_sq is None:
        centers_sq = jnp.sum(jnp.square(centers), axis=-1)
    x_sq = jnp.sum(jnp.square(x), axis=-1, keepdims=True)
    cross = jnp.matmul(x, centers.T, precision="highest")
    return jnp.maximum(x_sq - 2.0 * cross + centers_sq, 0.0)


@dataclasses.dataclass(frozen=True)
class RBFFeatureMap:
    """Gaussian / RBF hidden nodes g(w, b, x) = exp(-b ||x - w||^2)."""

    centers: jax.Array  # (L, D)
    gamma: jax.Array  # (L,), positive

    @property
    def in_dim(self) -> int:
        return self.centers.shape[1]

    @property
    def num_features(self) -> int:
        return self.centers.shape[0]

    def __call__(self, x: jax.Array) -> jax.Array:
        with phase("features"):
            return jnp.exp(-self.gamma * rbf_squared_dists(x, self.centers))


def make_random_features(
    key: jax.Array,
    in_dim: int,
    num_features: int,
    activation: str = "sigmoid",
    *,
    scale: float = 1.0,
    dtype=jnp.float32,
):
    """Sample the paper's uniform random hidden layer.

    The paper samples (w, b) uniformly; we use U(-scale, scale) for weights
    and U(0, scale) for biases (matching common ELM practice, e.g. Huang
    et al. 2006).
    """
    if activation == "rbf":
        kc, kg = jax.random.split(key)
        centers = jax.random.uniform(
            kc, (num_features, in_dim), minval=-scale, maxval=scale, dtype=dtype
        )
        gamma = jax.random.uniform(
            kg, (num_features,), minval=0.05, maxval=1.0, dtype=dtype
        )
        return RBFFeatureMap(centers=centers, gamma=gamma)
    if activation not in ACTIVATIONS:
        raise ValueError(
            f"unknown activation {activation!r}; valid: {sorted(valid_activations())}"
        )
    kw, kb = jax.random.split(key)
    w = jax.random.uniform(
        kw, (in_dim, num_features), minval=-scale, maxval=scale, dtype=dtype
    )
    b = jax.random.uniform(kb, (num_features,), minval=0.0, maxval=scale, dtype=dtype)
    return RandomFeatureMap(weights=w, bias=b, activation=activation)
