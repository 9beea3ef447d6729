"""Dispatching wrapper for the fused predict kernel.

Backend policy (mirrors elm_stats_ops):
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
and raises. The shared mapper is ``elm_stats_ops.scan_kwargs``.

Tuning policy (kernels/autotune.py): ``tuning="cached"`` (default)
consults the measured-winner cache (TUNED_kernels.json) for this
problem point and backend — explicit block kwargs always win, and a
cache miss keeps the hard-coded defaults, so cold-start behavior is
unchanged. ``tuning="off"`` never consults; ``tuning={...}`` applies
an explicit config dict.

``predict_map`` is the FeatureMap-level entry point every prediction
consumer routes through (``ELM.__call__``, ``dc_elm.node_predict``,
``serving.elm_server``): fusable affine/RBF maps take the fused path
when the result dtype is f32-or-narrower; f64 fidelity runs and
non-fusable maps (frozen deep backbones) materialize H for the call.

``predict_stacked`` is the multi-tenant twin: rows carry tenant ids
into a stacked (T, L, M) beta tensor, the shared hidden tile is
computed once per row and contracted against per-row gathered beta
tiles (``op="stacked"`` in the tuned cache; its scan fallback is a
jitted gather-then-contract over row chunks). One launch serves every
tenant in the batch — ``serving.elm_server`` in multi-tenant mode is
the request-level consumer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.elm_stats_ops import force_interpret, scan_kwargs


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def fused_predict(
    X, W, b, beta, *, activation: str = "sigmoid",
    use_kernel: bool | None = None, tuning="cached", **kw,
):
    """Y = g(X W + b) @ beta without materializing H.

    For activation="rbf" pass W = centers^T and b = gamma. Returns the
    oracle's result dtype (the promoted X/W/beta chain) with f32
    accumulation inside. ``tuning`` selects the block-knob policy (see
    module docstring).
    """
    from repro.kernels.elm_predict_ref import predict_dtype

    out_dtype = predict_dtype(X, W, beta)
    use = (_on_tpu() or force_interpret()) if use_kernel is None else use_kernel
    kw = autotune.resolve_config(
        kw, tuning, op="predict", impl="pallas" if use else "scan",
        N=X.shape[0], D=X.shape[1], L=W.shape[1], M=beta.shape[1],
        dtype=X.dtype,
    )
    if use:
        from repro.kernels.elm_predict import elm_predict_pallas

        if kw.get("chunk") is not None:
            raise ValueError(
                "chunk is the scan-fallback knob; the Pallas kernel "
                "takes block_n/block_l"
            )
        kw.pop("chunk", None)
        Y = elm_predict_pallas(
            X, W, b, beta, activation=activation,
            interpret=not _on_tpu(), **kw,
        )
        return Y.astype(out_dtype)
    from repro.kernels.elm_predict_ref import elm_predict_scan

    return elm_predict_scan(
        X, W, b, beta, activation=activation, **scan_kwargs(kw)
    ).astype(out_dtype)


def predict_map(
    x, feature_map, beta, *, use_kernel: bool | None = None,
    tuning="cached", **kw,
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
        tuning=tuning, **kw,
    )
    return Y.reshape(*lead, beta.shape[-1])


def fused_predict_stacked(
    X, W, b, betas, tenant_ids, *, activation: str = "sigmoid",
    use_kernel: bool | None = None, tuning="cached", **kw,
):
    """Y[n] = g(X W + b)[n] @ betas[tenant_ids[n]] without
    materializing H: one launch for a batch mixing many tenants.

    betas: (T, L, M) stacked per-tenant readouts over the ONE shared
    feature map; tenant_ids: (N,) int row -> tenant slot. Returns the
    oracle's promoted result dtype with f32 accumulation inside.
    """
    from repro.kernels.elm_predict_ref import stacked_dtype

    out_dtype = stacked_dtype(X, W, betas)
    use = (_on_tpu() or force_interpret()) if use_kernel is None else use_kernel
    kw = autotune.resolve_config(
        kw, tuning, op="stacked", impl="pallas" if use else "scan",
        N=X.shape[0], D=X.shape[1], L=W.shape[1], M=betas.shape[2],
        dtype=X.dtype, T=betas.shape[0],
    )
    if use:
        from repro.kernels.elm_predict import elm_predict_stacked_pallas

        if kw.get("chunk") is not None:
            raise ValueError(
                "chunk is the scan-fallback knob; the Pallas kernel "
                "takes block_n/block_l"
            )
        kw.pop("chunk", None)
        Y = elm_predict_stacked_pallas(
            X, W, b, betas, tenant_ids, activation=activation,
            interpret=not _on_tpu(), **kw,
        )
        return Y.astype(out_dtype)
    from repro.kernels.elm_predict_ref import elm_predict_stacked_scan

    return elm_predict_stacked_scan(
        X, W, b, betas, tenant_ids, activation=activation,
        **scan_kwargs(kw),
    ).astype(out_dtype)


def predict_stacked(
    x, feature_map, betas, tenant_ids, *,
    use_kernel: bool | None = None, tuning="cached", **kw,
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
        use_kernel=use_kernel, tuning=tuning, **kw,
    )
