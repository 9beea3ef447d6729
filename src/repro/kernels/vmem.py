"""The VMEM a Pallas kernel may request, shared by the kernels that size
their blocks or choose their grid from it (elm_gossip, elm_stats) and
by the gossip dispatcher's choice of arm."""

from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

#: VMEM per TensorCore of TPU v5e, the chip this repo targets, for when
#: the default device is no TPU (source: the "TPU v5 lite" entry of
#: ``jax.experimental.pallas.tpu.get_tpu_info``, JAX 0.9)
_V5E_VMEM_BYTES = 128 * 2**20

#: the scoped VMEM limit a Mosaic kernel runs under unless its compiler
#: params raise it: ``vmem_params`` raises it only past this, and the
#: gossip dispatcher keeps its multi-round arm to what fits in it
DEFAULT_SCOPED_VMEM = 16 * 2**20


def vmem_budget() -> int:
    """The VMEM a kernel's resident set may request: half the core's
    capacity, the other half left to Mosaic's own scratch and the
    ``vmem_params`` margin. Read from the default device where JAX
    describes it, else v5e's capacity (off TPU the budget only shapes
    interpret-mode runs, whose outputs do not depend on it)."""
    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:  # not a TPU device kind
        capacity = _V5E_VMEM_BYTES
    return capacity // 2


def vmem_params(nbytes: int):
    """Raise the scoped VMEM limit to the resident set plus a 4 MiB
    margin when that passes ``DEFAULT_SCOPED_VMEM``. The default is
    only a starting point: resident sets are held to ``vmem_budget()``,
    the most this may ask for."""
    limit = nbytes + 4 * 2**20
    if limit <= DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(limit))
