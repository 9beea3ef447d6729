"""Pure-jnp oracles for the fused predict kernel.

Four references:

* ``predict_reference`` — the semantic oracle: materialize H, then
  H @ beta. What the fused kernel must match.
* ``elm_predict_scan`` — the *streaming* jnp implementation: lax.scan
  over (chunk, D) row tiles, each producing its (chunk, M) output
  slice, so peak memory is the chunk working set, not the (N, L)
  hidden matrix. This is the fused path on backends without the Pallas
  kernel (CPU jit).
* ``predict_stacked_reference`` / ``elm_predict_stacked_scan`` — the
  multi-tenant twins: every row carries a tenant id into a stacked
  (T, L, M) beta tensor and the per-row readout is

      Y[n] = H[n] @ betas[tenant_ids[n]]

  (decentralized multi-task ELM, arXiv 1904.11366: many per-task
  readouts over ONE shared hidden layer). The gather-then-contract is
  a batched dot_general, identical between the oracle and the scan so
  the single-chunk scan degenerates to the oracle bitwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.elm_stats_ref import hidden_reference


def predict_dtype(X, W, beta) -> jnp.dtype:
    """The oracle's result dtype: the promoted operand chain."""
    return jnp.promote_types(jnp.promote_types(X.dtype, W.dtype), beta.dtype)


def predict_reference(X, W, b, beta, *, activation="sigmoid"):
    """Y via materialized H — the unfused two-pass pipeline."""
    H = hidden_reference(X, W, b, activation)
    op = jnp.promote_types(H.dtype, beta.dtype)
    return jax.lax.dot_general(
        H.astype(op), beta.astype(op),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(predict_dtype(X, W, beta))


@functools.partial(jax.jit, static_argnames=("activation", "chunk"))
def elm_predict_scan(X, W, b, beta, *, activation="sigmoid", chunk=4096):
    """Y streamed over N in `chunk`-row tiles (H never full-size).

    Ragged tails are zero-padded; the padded rows produce g(0)-valued
    hidden rows whose outputs are simply sliced off (unlike the moment
    kernel, predict needs no masking for correctness — no cross-row
    reduction — but the result rows past N are discarded all the same).
    """
    N, D = X.shape
    M = beta.shape[1]
    if N == 0:  # nothing to scan over
        op = jnp.promote_types(
            jnp.promote_types(X.dtype, W.dtype), beta.dtype
        )
        return jnp.zeros((0, M), op)
    chunk = min(chunk, N)
    op = jnp.promote_types(
        jnp.promote_types(X.dtype, W.dtype), beta.dtype
    )
    beta_op = beta.astype(op)
    if chunk == N:
        # single-chunk point: one fused jit, no scan machinery —
        # bitwise-identical to the one-step scan
        h = hidden_reference(X, W, b, activation).astype(op)
        return jax.lax.dot_general(
            h, beta_op,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(op)
    pN = (-N) % chunk
    if pN:
        X = jnp.pad(X, ((0, pN), (0, 0)))
    K = X.shape[0] // chunk
    Xc = X.reshape(K, chunk, D)

    def step(_, x):
        h = hidden_reference(x, W, b, activation).astype(op)
        y = jax.lax.dot_general(
            h, beta_op,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return None, y.astype(op)

    _, Yc = jax.lax.scan(step, None, Xc)
    return Yc.reshape(K * chunk, M)[:N]


# ---------------------------------------------------------------------------
# Stacked multi-tenant readouts (one shared hidden layer, T betas)
# ---------------------------------------------------------------------------


def stacked_dtype(X, W, betas) -> jnp.dtype:
    """Result dtype of the stacked oracle: the promoted operand chain."""
    return jnp.promote_types(
        jnp.promote_types(X.dtype, W.dtype), betas.dtype
    )


def _gather_contract(h, betas, tenant_ids):
    """Y[n] = h[n] @ betas[tenant_ids[n]] as one batched dot_general.

    The gathered (n, L, M) beta tiles contract against the hidden rows
    batch-wise; the SAME op in the oracle, the scan and the Pallas
    kernel, so per-row results are independent of how rows are packed
    into a launch (the differential-serving bitwise guarantee).
    """
    bg = jnp.take(betas, tenant_ids, axis=0)  # (n, L, M)
    y = jax.lax.dot_general(
        h[:, None, :], bg,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return y[:, 0, :]


def predict_stacked_reference(
    X, W, b, betas, tenant_ids, *, activation="sigmoid"
):
    """Multi-tenant oracle: materialized H, gather-then-contract.

    X: (N, D), betas: (T, L, M), tenant_ids: (N,) int into the T axis.
    """
    H = hidden_reference(X, W, b, activation)
    op = jnp.promote_types(H.dtype, betas.dtype)
    ids = jnp.asarray(tenant_ids, jnp.int32)
    return _gather_contract(
        H.astype(op), betas.astype(op), ids
    ).astype(stacked_dtype(X, W, betas))


@functools.partial(jax.jit, static_argnames=("activation", "chunk"))
def elm_predict_stacked_scan(
    X, W, b, betas, tenant_ids, *, activation="sigmoid", chunk=2048
):
    """Stacked predict streamed over N in `chunk`-row tiles.

    Peak memory is one chunk's working set — dominated by the gathered
    (chunk, L, M) beta tiles, which is why the default chunk sits below
    the single-tenant scan's. At ``chunk >= N`` this degenerates to the
    single fused program (bitwise the oracle's gather-then-contract).
    """
    N, D = X.shape
    M = betas.shape[2]
    op = stacked_dtype(X, W, betas)
    if N == 0:
        return jnp.zeros((0, M), op)
    ids = jnp.asarray(tenant_ids, jnp.int32)
    chunk = min(chunk, N)
    betas_op = betas.astype(op)
    if chunk == N:
        h = hidden_reference(X, W, b, activation).astype(op)
        return _gather_contract(h, betas_op, ids).astype(op)
    pN = (-N) % chunk
    if pN:
        X = jnp.pad(X, ((0, pN), (0, 0)))
        ids = jnp.pad(ids, (0, pN))  # id 0: sliced off below
    K = X.shape[0] // chunk
    Xc = X.reshape(K, chunk, D)
    idc = ids.reshape(K, chunk)

    def step(_, xi):
        x, i = xi
        h = hidden_reference(x, W, b, activation).astype(op)
        return None, _gather_contract(h, betas_op, i).astype(op)

    _, Yc = jax.lax.scan(step, None, (Xc, idc))
    return Yc.reshape(K * chunk, M)[:N]
