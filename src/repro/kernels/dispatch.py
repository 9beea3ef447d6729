"""The one dispatch policy of the kernel wrappers (``*_ops.py``).

A wrapper whose caller leaves ``use_kernel=None`` takes its Pallas
kernel on a TPU, and its jitted scan or jnp fallback elsewhere unless
``REPRO_FORCE_INTERPRET`` asks for the kernel (then run in interpret
mode). An explicit ``use_kernel=`` always wins. The Pallas grids take
``block_n``/``block_l`` and the scans ``chunk``; ``scan_kwargs`` and
``pallas_kwargs`` fit a caller's block keywords to the arm that runs.
``attn_ops`` and ``ssd_ops`` read ``on_tpu()`` alone: the forced
interpret leg does not cover their kernels.
"""

from __future__ import annotations

import os

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def force_interpret() -> bool:
    """True when CI's pallas-interpret leg forces the kernel paths.

    ``REPRO_FORCE_INTERPRET=1`` makes every kernel dispatcher whose
    caller left ``use_kernel=None`` take its Pallas branch (interpret
    mode off-TPU), so the kernel code paths are exercised on CPU
    runners instead of only the scan/oracle fallbacks.
    """
    return os.environ.get("REPRO_FORCE_INTERPRET", "").strip() not in ("", "0")


def wants_kernel(use_kernel: bool | None) -> bool:
    """Whether a dispatcher takes its Pallas arm."""
    if use_kernel is not None:
        return use_kernel
    return on_tpu() or force_interpret()


def scan_kwargs(kw: dict) -> dict:
    """Map Pallas block kwargs onto the scan fallback's ``chunk``.

    block_n -> chunk (same streaming role); block_l has no scan
    meaning and raises; both block_n and chunk is a conflict.
    """
    kw = dict(kw)
    if kw.get("block_l") is not None:
        raise ValueError(
            "block_l is a Pallas grid knob with no scan-fallback "
            "equivalent (the scan computes all L hidden columns per "
            "chunk); pass chunk= (or block_n=, which maps to chunk) "
            "instead, or drop block_l"
        )
    kw.pop("block_l", None)
    block_n = kw.pop("block_n", None)
    if block_n is not None:
        if kw.get("chunk") is not None:
            raise ValueError(
                f"both block_n={block_n} and chunk={kw['chunk']} were "
                "passed to the scan fallback; block_n maps to chunk — "
                "pass exactly one"
            )
        kw["chunk"] = block_n
    return kw


def pallas_kwargs(kw: dict) -> dict:
    """The block kwargs of a Pallas call: a set ``chunk``, the scan's
    knob, raises; an unset one is dropped."""
    kw = dict(kw)
    if kw.pop("chunk", None) is not None:
        raise ValueError(
            "chunk is the scan-fallback knob; the Pallas kernel takes "
            "block_n/block_l"
        )
    return kw
