"""The plain reference the benchmark holds the program to.

It imports nothing of the program. Data come from the seed (MNIST-shaped
rows with a linear teacher), the network from the configuration's
construction, and every contraction runs at an explicit precision:

* ``"highest"``: float32 at ``precision=HIGHEST``, the reference;
* ``"high"``: three bf16 passes (operands split into a bf16 high and low
  part, the low-by-low product dropped, float32 accumulation). This is
  the control: the step below the float32-at-HIGHEST the program
  states. It is spelled out here, so it means the same on every
  backend; the split rounds with integer operations, because XLA on the
  TPU keeps excess precision through a fused float32 -> bfloat16 ->
  float32 round trip, which left the low part zero and the control a
  single bf16 pass.

The centralized ridge solution beta* is solved in float64 on the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ACTIVATIONS = {"sigmoid": jax.nn.sigmoid}


def make_data(key, lead, D, M):
    """Pixel-like inputs in [0, 1) and one-hot labels of a random
    linear teacher: an MNIST-shaped classification task."""
    kx, kt = jax.random.split(key)
    X = jax.random.uniform(kx, (*lead, D), jnp.float32)
    teacher = jax.random.normal(kt, (D, M), jnp.float32)
    labels = jnp.argmax(jnp.dot(X - 0.5, teacher, precision="highest"), axis=-1)
    return X, jax.nn.one_hot(labels, M, dtype=jnp.float32)


def make_features(key, D, L, scale):
    """The shared random hidden layer: W ~ U(-s, s), b ~ U(0, s)."""
    kw, kb = jax.random.split(key)
    W = jax.random.uniform(kw, (D, L), jnp.float32, -scale, scale)
    b = jax.random.uniform(kb, (L,), jnp.float32, 0.0, scale)
    return W, b


def random_geometric(V: int, radius: float, seed: int) -> np.ndarray:
    """Adjacency of the paper's random geometric network: V points
    uniform on the unit square, linked when closer than ``radius``;
    redrawn until connected."""
    rng = np.random.default_rng(seed)
    for _ in range(200):
        pts = rng.uniform(size=(V, 2))
        d = np.linalg.norm(pts[:, None, :] - pts[None, :, :], axis=-1)
        adj = ((d < radius) & ~np.eye(V, dtype=bool)).astype(np.float32)
        if _connected(adj):
            return adj
    raise ValueError(f"no connected graph for V={V}, radius={radius}")


def ring(V: int) -> np.ndarray:
    adj = np.zeros((V, V), np.float32)
    for i in range(V):
        adj[i, (i + 1) % V] = adj[(i + 1) % V, i] = 1.0
    return adj


def _connected(adj: np.ndarray) -> bool:
    seen = np.zeros(len(adj), bool)
    seen[0] = True
    frontier = [0]
    while frontier:
        nxt = np.nonzero(adj[frontier].any(axis=0) & ~seen)[0]
        seen[nxt] = True
        frontier = list(nxt)
    return bool(seen.all())


def network(cfg: dict) -> np.ndarray:
    """The configuration's fixed communication graph."""
    g = cfg["graph"]
    if g["kind"] == "random_geometric":
        return random_geometric(cfg["V"], g["radius"], g["seed"])
    if g["kind"] == "ring":
        return ring(cfg["V"])
    raise ValueError(f"unknown graph kind {g['kind']!r}")


def consensus_error(betas):
    """Max over nodes of ||beta_i - mean beta|| / (1 + ||mean beta||)."""
    mean = jnp.mean(betas, axis=0, keepdims=True)
    num = jnp.max(jnp.sqrt(jnp.sum((betas - mean) ** 2, axis=(1, 2))))
    return num / (1.0 + jnp.sqrt(jnp.sum(mean**2)))


def distance_to(betas: np.ndarray, target: np.ndarray) -> float:
    """Max over nodes of ||beta_i - beta*|| / (1 + ||beta*||), in float64."""
    b = np.asarray(betas, np.float64)
    t = np.asarray(target, np.float64)
    num = np.sqrt(np.sum((b - t[None]) ** 2, axis=(1, 2)))
    return float(np.max(num) / (1.0 + np.sqrt(np.sum(t**2))))


# ---------------------------------------------------------------------------
# Contractions at a stated precision
# ---------------------------------------------------------------------------


def _round_bf16(x):
    """float32 rounded to bfloat16's 8 significant bits (to nearest,
    ties to even), still float32: integer operations on the bits, which
    no compiler folds away."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _split(x):
    hi = _round_bf16(x)
    lo = _round_bf16(x - hi)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def einsum(spec: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        (ah, al), (bh, bl) = _split(a), _split(b)

        def dot(x, y):
            return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

        return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


def features(X, W, b, activation: str, precision: str = "highest"):
    return ACTIVATIONS[activation](einsum("...d,dl->...l", X, W, precision) + b)


@functools.partial(jax.jit, static_argnames=("activation", "precision"))
def node_moments(X, T, W, b, *, activation, precision="highest"):
    """(P, Q) of each node in a block: X (B, N, D), T (B, N, M)."""
    H = features(X, W, b, activation, precision)
    return (
        einsum("vnl,vnk->vlk", H, H, precision),
        einsum("vnl,vnm->vlm", H, T, precision),
    )


@jax.jit
def omegas_from(P, ridge):
    """(I * ridge + P_i)^-1 per node."""
    eye = jnp.eye(P.shape[-1], dtype=P.dtype)
    with jax.default_matmul_precision("highest"):
        return jnp.linalg.inv(P + ridge * eye)


@jax.jit
def _resid(omega, P, ridge):
    L = P.shape[-1]
    eye = jnp.eye(L, dtype=P.dtype)
    prod = jnp.einsum(
        "vlk,vkj->vlj", omega, P + ridge * eye,
        precision=jax.lax.Precision.HIGHEST,
    )
    return jnp.max(jnp.abs(prod - eye))


def omega_residual(omegas, P, ridge: float, block: int = 16) -> float:
    """Max over nodes of max |Omega_i (P_i + ridge I) - I|."""
    worst = 0.0
    for s in range(0, omegas.shape[0], block):
        worst = max(worst, float(_resid(omegas[s:s + block], P[s:s + block], ridge)))
    return worst


@jax.jit
def gram_times(P, betas, ridge):
    """sum_i (P_i + ridge I) beta_i at HIGHEST."""
    return jnp.einsum(
        "vlk,vkm->lm", P, betas, precision=jax.lax.Precision.HIGHEST
    ) + ridge * jnp.sum(betas, axis=0)


def rel_err(got, want) -> float:
    """max |got - want| / max |want| over a whole array, in float64."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def node_rel_err(got, want) -> float:
    """The worst node's relative error, each node against its own scale."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    axes = tuple(range(1, want.ndim))
    num = np.max(np.abs(got - want), axis=axes)
    den = np.maximum(np.max(np.abs(want), axis=axes), 1e-30)
    return float(np.max(num / den))


def node_median_err(got, want) -> float:
    """The worst node's median error, each node against its own scale:
    a bias shared by all entries moves it, rounding spread over a few
    entries does not."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    axes = tuple(range(1, want.ndim))
    num = np.median(np.abs(got - want).reshape(len(want), -1), axis=1)
    den = np.maximum(np.max(np.abs(want), axis=axes), 1e-30)
    return float(np.max(num / den))


def beta_star(P_total, Q_total, C: float) -> np.ndarray:
    """Centralized ridge solution (I/C + sum P_i)^-1 sum Q_i in float64."""
    P64 = np.asarray(P_total, np.float64)
    A = P64 + np.eye(P64.shape[0]) / C
    return np.linalg.solve(A, np.asarray(Q_total, np.float64))


def numpy_predict(W, b, x, beta, activation: str = "sigmoid") -> np.ndarray:
    """g(x W + b) beta in float64 on the host."""
    if activation != "sigmoid":
        raise ValueError(f"no float64 reference for {activation!r}")
    z = np.asarray(x, np.float64) @ np.asarray(W, np.float64) + np.asarray(b, np.float64)
    return (1.0 / (1.0 + np.exp(-z))) @ np.asarray(beta, np.float64)


@functools.partial(jax.jit, static_argnames=("activation", "precision"))
def predict(x, W, b, beta, *, activation="sigmoid", precision="highest"):
    """The reference's served rows on the device (the serving control)."""
    return einsum("nl,lm->nm", features(x, W, b, activation, precision), beta, precision)
