"""Dispatcher for the attention kernel (TPU kernel vs jnp chunked path).

models/attention.py calls its own chunked jnp implementation directly on
non-TPU backends (it supports windows and mixed local/global); this
wrapper exposes the Pallas kernel for TPU runs and for interpret-mode
validation against the oracle.
"""

from __future__ import annotations

from repro.kernels.attn_ref import attention_reference
from repro.kernels.dispatch import on_tpu


def causal_attention(q, k, v, *, softcap: float = 0.0, use_kernel=None, **kw):
    use = on_tpu() if use_kernel is None else use_kernel
    if use:
        from repro.kernels.attn import flash_attention_pallas

        return flash_attention_pallas(
            q, k, v, softcap=softcap, interpret=not on_tpu(), **kw
        )
    return attention_reference(q, k, v, softcap=softcap)


def decode_attention(
    q, cache_k, cache_v, pos, *, window=None, softcap: float = 0.0,
    use_kernel=None, **kw,
):
    """Single-token attention over a KV cache (flash-decode on TPU)."""
    use = on_tpu() if use_kernel is None else use_kernel
    if use:
        from repro.kernels.decode_attn import flash_decode_pallas

        return flash_decode_pallas(
            q, cache_k, cache_v, pos, window=window, softcap=softcap,
            interpret=not on_tpu(), **kw,
        )
    from repro.models.attention import decode_attend

    return decode_attend(
        q, cache_k, cache_v, pos, windowed=False, window=window, cap=softcap
    )
