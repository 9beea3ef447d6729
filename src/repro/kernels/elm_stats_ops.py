"""Dispatching wrapper for the fused feature->moment kernel.

Backend policy (``kernels/dispatch.py``):
  * TPU              -> the Pallas kernel (H never touches HBM)
  * use_kernel=True elsewhere -> the kernel in interpret mode
    (correctness path for tests; slow)
  * otherwise        -> ``elm_stats_scan``, the jitted lax.scan
    streaming implementation — fused-by-construction on CPU/GPU (peak
    memory is one chunk's working set, not the (N, L) hidden matrix)

Block-knob mapping (Pallas grid -> scan fallback):
  * ``block_n`` (rows per grid tile) maps to the scan's ``chunk`` —
    both are "rows resident per streaming step", and with
    chunk == block_n the two paths accumulate in the same f32 order
    (bitwise-pinned in tests/test_stats.py). Passing both ``block_n``
    and ``chunk`` to the scan path is a conflict and raises.
  * ``block_l`` (hidden columns per grid tile) has NO scan equivalent:
    the scan computes all L hidden columns per chunk in one matmul.
    Passing a non-None ``block_l`` to the scan path raises instead of
    being silently dropped.
"""

from __future__ import annotations

from repro.kernels.dispatch import (
    on_tpu,
    pallas_kwargs,
    scan_kwargs,
    wants_kernel,
)


def fused_moments(
    X, W, b, T, *, activation: str = "sigmoid",
    use_kernel: bool | None = None, **kw,
):
    """(P, Q) f32 from raw inputs without materializing H.

    For activation="rbf" pass W = centers^T and b = gamma.
    """
    if wants_kernel(use_kernel):
        from repro.kernels.elm_stats import elm_stats_pallas

        return elm_stats_pallas(
            X, W, b, T, activation=activation,
            interpret=not on_tpu(), **pallas_kwargs(kw),
        )
    from repro.kernels.elm_stats_ref import elm_stats_scan

    return elm_stats_scan(X, W, b, T, activation=activation, **scan_kwargs(kw))


def fused_preact_moments(
    Z, b, T, *, activation: str = "sigmoid",
    use_kernel: bool | None = None, **kw,
):
    """(P, Q) f32 from an assembled preactivation without materializing H.

    The vertical-mode entry: Z = sum_i X_i W_i was already reduced
    across column-sliced nodes (core/vertical.py), so the kernel only
    applies bias + activation per tile before the moment accumulation.
    Same backend policy as ``fused_moments``; "rbf" is rejected
    (no additive preactivation form).
    """
    if activation == "rbf":
        raise ValueError(
            "rbf has no preactivation form; vertical mode supports "
            "RandomFeatureMap activations only"
        )
    if wants_kernel(use_kernel):
        from repro.kernels.elm_stats import elm_preact_stats_pallas

        return elm_preact_stats_pallas(
            Z, b, T, activation=activation,
            interpret=not on_tpu(), **pallas_kwargs(kw),
        )
    from repro.kernels.elm_stats_ref import preact_stats_scan

    return preact_stats_scan(
        Z, b, T, activation=activation, **scan_kwargs(kw)
    )
