"""Dispatching wrappers for the Gram kernels (TPU kernel vs jnp oracle)."""

from __future__ import annotations

from repro.kernels.dispatch import on_tpu, wants_kernel
from repro.kernels.gram_ref import cross_reference, gram_reference


def gram(H, *, use_kernel: bool | None = None, **kw):
    if wants_kernel(use_kernel):
        from repro.kernels.gram import gram_pallas

        return gram_pallas(H, interpret=not on_tpu(), **kw)
    return gram_reference(H)


def cross(H, T, *, use_kernel: bool | None = None, **kw):
    if wants_kernel(use_kernel):
        from repro.kernels.gram import cross_pallas

        return cross_pallas(H, T, interpret=not on_tpu(), **kw)
    return cross_reference(H, T)


def local_elm_stats(H, T, *, use_kernel: bool | None = None):
    """(P, Q) = (H^T H, H^T T) — one DC-ELM node's sufficient statistics."""
    return gram(H, use_kernel=use_kernel), cross(H, T, use_kernel=use_kernel)
