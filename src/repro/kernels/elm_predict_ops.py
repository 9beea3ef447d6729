"""Dispatching wrapper for the fused predict kernel.

Backend policy (``kernels/dispatch.py``):
  * TPU              -> the Pallas kernel (H never touches HBM)
  * use_kernel=True elsewhere -> the kernel in interpret mode
    (correctness path for tests; slow)
  * otherwise        -> ``elm_predict_scan``, the jitted lax.scan
    streaming implementation — fused-by-construction on CPU/GPU (peak
    memory is one chunk's working set, not the (N, L) hidden matrix)

Block-knob mapping (Pallas grid -> scan fallback): ``block_n`` maps to
the scan's ``chunk`` (rows resident per streaming step); ``block_l``
has no scan equivalent (the scan computes all L hidden columns per
chunk) and a non-None value raises instead of being silently dropped.
Passing both ``block_n`` and ``chunk`` to the scan path is a conflict
and raises (``dispatch.scan_kwargs``).

``predict_map`` is the FeatureMap-level entry point every prediction
consumer routes through (``ELM.__call__``, ``dc_elm.node_predict``,
``serving.elm_server``): fusable affine/RBF maps take the fused path
when the result dtype is f32-or-narrower; f64 fidelity runs and
non-fusable maps (frozen deep backbones) materialize H for the call.

``predict_stacked`` is the multi-tenant twin: rows carry tenant ids
into a stacked (T, L, M) beta tensor, the shared hidden tile is
computed once per row and contracted against per-row gathered beta
tiles (its scan fallback is a jitted gather-then-contract over row
chunks). One launch serves every tenant in the batch —
``serving.elm_server`` in multi-tenant mode is the request-level
consumer.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dispatch import (
    on_tpu,
    pallas_kwargs,
    scan_kwargs,
    wants_kernel,
)


def fused_predict(
    X, W, b, beta, *, activation: str = "sigmoid",
    use_kernel: bool | None = None, **kw,
):
    """Y = g(X W + b) @ beta without materializing H.

    For activation="rbf" pass W = centers^T and b = gamma. Returns the
    oracle's result dtype (the promoted X/W/beta chain) with f32
    accumulation inside.
    """
    from repro.kernels.elm_predict_ref import predict_dtype

    out_dtype = predict_dtype(X, W, beta)
    if wants_kernel(use_kernel):
        from repro.kernels.elm_predict import elm_predict_pallas

        Y = elm_predict_pallas(
            X, W, b, beta, activation=activation,
            interpret=not on_tpu(), **pallas_kwargs(kw),
        )
        return Y.astype(out_dtype)
    from repro.kernels.elm_predict_ref import elm_predict_scan

    return elm_predict_scan(
        X, W, b, beta, activation=activation, **scan_kwargs(kw)
    ).astype(out_dtype)


def predict_map(
    x, feature_map, beta, *, use_kernel: bool | None = None, **kw,
):
    """f(x) = h(x) @ beta for any FeatureMap, fused where fusable.

    x: (..., D) with arbitrary leading dims (flattened to rows for the
    kernel and restored). feature_map=None means x already *is* the
    (materialized) feature matrix — the serving path for deep-backbone
    heads, where the hidden layer cannot be refused into the kernel.
    """
    from repro.core.stats import fusable_params

    if feature_map is None:
        return jnp.matmul(x, beta, precision="highest")
    params = fusable_params(feature_map)
    if params is None or jnp.result_type(x, beta) == jnp.float64:
        # non-fusable map (deep backbone) or the f64 fidelity path:
        # materialize H for this call only
        return jnp.matmul(feature_map(x), beta, precision="highest")
    W, b, activation = params
    lead = x.shape[:-1]
    rows = x.reshape(-1, x.shape[-1])
    if rows.shape[0] == 0:  # the tiled paths cannot grid over N = 0
        return jnp.matmul(feature_map(x), beta, precision="highest")
    Y = fused_predict(
        rows, W, b, beta, activation=activation, use_kernel=use_kernel,
        **kw,
    )
    return Y.reshape(*lead, beta.shape[-1])


def fused_predict_stacked(
    X, W, b, betas, tenant_ids, *, activation: str = "sigmoid",
    use_kernel: bool | None = None, **kw,
):
    """Y[n] = g(X W + b)[n] @ betas[tenant_ids[n]] without
    materializing H: one launch for a batch mixing many tenants.

    betas: (T, L, M) stacked per-tenant readouts over the ONE shared
    feature map; tenant_ids: (N,) int row -> tenant slot. Returns the
    oracle's promoted result dtype with f32 accumulation inside.
    """
    from repro.kernels.elm_predict_ref import stacked_dtype

    out_dtype = stacked_dtype(X, W, betas)
    if wants_kernel(use_kernel):
        from repro.kernels.elm_predict import elm_predict_stacked_pallas

        Y = elm_predict_stacked_pallas(
            X, W, b, betas, tenant_ids, activation=activation,
            interpret=not on_tpu(), **pallas_kwargs(kw),
        )
        return Y.astype(out_dtype)
    from repro.kernels.elm_predict_ref import elm_predict_stacked_scan

    return elm_predict_stacked_scan(
        X, W, b, betas, tenant_ids, activation=activation,
        **scan_kwargs(kw),
    ).astype(out_dtype)


def predict_stacked(
    x, feature_map, betas, tenant_ids, *,
    use_kernel: bool | None = None, **kw,
):
    """f_t(x) = h(x) @ betas[t] per row, fused where fusable.

    The multi-tenant ``predict_map``: x (N, D) rows, betas (T, L, M),
    tenant_ids (N,) int. feature_map=None means x already IS the
    feature matrix (deep-backbone serving); non-fusable maps and the
    f64 fidelity path materialize H and gather-contract per row.
    """
    from repro.core.stats import fusable_params
    from repro.kernels.elm_predict_ref import _gather_contract

    ids = jnp.asarray(tenant_ids, jnp.int32)
    if feature_map is None:
        op = jnp.promote_types(x.dtype, betas.dtype)
        return _gather_contract(
            x.astype(op), betas.astype(op), ids
        ).astype(op)
    params = fusable_params(feature_map)
    if (
        params is None
        or jnp.result_type(x, betas) == jnp.float64
        or x.shape[0] == 0  # the tiled paths cannot grid over N = 0
    ):
        H = feature_map(x)
        op = jnp.promote_types(H.dtype, betas.dtype)
        return _gather_contract(
            H.astype(op), betas.astype(op), ids
        ).astype(op)
    W, b, activation = params
    return fused_predict_stacked(
        x, W, b, betas, ids, activation=activation,
        use_kernel=use_kernel, **kw,
    )
