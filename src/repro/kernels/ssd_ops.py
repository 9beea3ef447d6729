"""Dispatching wrapper for the SSD scan.

``ssd`` picks the Pallas TPU kernel when running on TPU (or when forced
via ``use_kernel=True`` with interpret mode on CPU) and otherwise the
pure-jnp chunked oracle — identical semantics, so the model code never
branches.
"""

from __future__ import annotations

from repro.kernels.dispatch import on_tpu
from repro.kernels.ssd_ref import ssd_reference


def ssd(
    x,
    dt,
    A,
    B,
    C,
    *,
    chunk: int = 256,
    initial_state=None,
    use_kernel: bool | None = None,
):
    use = on_tpu() if use_kernel is None else use_kernel
    if use:
        from repro.kernels.ssd_scan import ssd_pallas

        return ssd_pallas(
            x,
            dt,
            A,
            B,
            C,
            chunk=chunk,
            initial_state=initial_state,
            interpret=not on_tpu(),
        )
    return ssd_reference(x, dt, A, B, C, chunk=chunk, initial_state=initial_state)
