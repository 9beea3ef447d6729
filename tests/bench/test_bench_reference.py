"""The plain reference: its control precision and its float64 Q."""

import jax
import jax.numpy as jnp
import numpy as np
from bench_tiny import REPO  # noqa: F401  (puts the repository on sys.path)

from bench import network, reference


def test_split_rounds_like_bfloat16():
    """The integer rounding gives the bfloat16 conversion's high and low
    parts bit for bit, ties to even included."""
    x = jax.random.normal(jax.random.key(0), (4096,), jnp.float32) * 1e3
    ties = jnp.asarray([1.0 + 2.0**-8, 1.0 + 3 * 2.0**-8, -(1.0 + 2.0**-8)], jnp.float32)
    x = jnp.concatenate([x, ties])
    hi, lo = reference._split(x)
    want_hi = x.astype(jnp.bfloat16)
    want_lo = (x - want_hi.astype(jnp.float32)).astype(jnp.bfloat16)
    assert bool(jnp.all(hi == want_hi)) and bool(jnp.all(lo == want_lo))


def test_three_passes_sit_between_one_pass_and_float64():
    a = jax.random.uniform(jax.random.key(1), (64, 784), jnp.float32)
    b = jax.random.uniform(jax.random.key(2), (784, 32), jnp.float32, -0.1, 0.1)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    high = reference.rel_err(reference.einsum("nd,dl->nl", a, b, "high"), want)
    one = reference.rel_err(
        jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32), want,
    )
    assert 1e-7 < high < 1e-4 < one


def test_reference_q_is_float64_over_row_blocks():
    """Q comes back in float64, summed over blocks of ``Q_ROWS`` rows,
    and agrees with a float64 computation of the same features."""
    V, N, D, L, M = 2, 2 * network.Q_ROWS + 16, 8, 4, 3
    X, T = reference.make_data(jax.random.key(3), (V, N), D, M)
    W, b = reference.make_features(jax.random.key(4), D, L, 0.5)
    P_, Q_ = network._node_moments(X, T, W, b, "sigmoid", "highest", 4096)
    assert Q_.dtype == np.float64 and Q_.shape == (V, L, M) and P_.shape == (V, L, L)
    H = np.stack([reference.numpy_predict(W, b, X[v], np.eye(L)) for v in range(V)])
    want = np.einsum("vnl,vnm->vlm", H, np.asarray(T, np.float64))
    assert reference.rel_err(Q_, want) < 1e-6
