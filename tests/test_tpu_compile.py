"""Compile the ELM Pallas kernels for a described TPU v5e, chip-free.

Each case lowers one kernel at the widths ``chip_smoke.py`` runs and
compiles it with the TPU compiler for a ``v5e:2x2`` topology that is
described, not attached. What Mosaic refuses (unsupported gathers,
MXU transforms, scoped-VMEM overflows) fails here instead of on the
chip. The kernels are called directly with ``interpret=False``: the
dispatchers ask ``jax.default_backend()``, which is the CPU here.

The topology is described inside a fixture, never at import time, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import elm_gossip
from repro.kernels.elm_predict import (
    elm_predict_pallas,
    elm_predict_stacked_pallas,
)
from repro.kernels.elm_stats import elm_preact_stats_pallas, elm_stats_pallas
from repro.kernels.vmem import vmem_budget

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _gossip_args(V, L, M, d, S=1):
    return [
        ((V, L, M), F32), ((V, L, L), F32), ((S, V, d), I32),
        ((S, V, d), F32), ((S, V), F32), ((), F32),
    ]


def _round(V, L, M, **kw):
    """The per-round arm at the node block the dispatcher picks."""

    def call(*a):
        bv = elm_gossip.fit_block_v(V, L, M, 8, vmem_budget())
        return elm_gossip.elm_gossip_pallas(
            *a, num_rounds=3, block_v=bv, **kw
        )

    return call


# name -> (kernel, [(shape, dtype), ...]) at the chip_smoke.py widths
CASES = {
    # learn phase: one node's stats pass, N_i=4096, D=784, L=1024, M=10
    "stats_f32": (
        elm_stats_pallas,
        [((4096, 784), F32), ((784, 1024), F32), ((1024,), F32),
         ((4096, 10), F32)],
    ),
    # the same pass batched over 64 nodes, as stream_init runs it
    "stats_f32_nodes64": (
        jax.vmap(elm_stats_pallas, in_axes=(0, None, None, 0)),
        [((64, 4096, 784), F32), ((784, 1024), F32), ((1024,), F32),
         ((64, 4096, 10), F32)],
    ),
    # mnist64.learn's own shape: 64 nodes of 16,384 rows, L = 1024
    "stats_f32_mnist64": (
        jax.vmap(elm_stats_pallas, in_axes=(0, None, None, 0)),
        [((64, 16384, 784), F32), ((784, 1024), F32), ((1024,), F32),
         ((64, 16384, 10), F32)],
    ),
    # the sharded engine: one node of 2^20 rows a chip, as stream_init's
    # shard_map runs it (its vmap over the one node the chip holds), in
    # blocks of elm_stats.BLOCK_ROWS rows
    "stats_f32_silo": (
        jax.vmap(elm_stats_pallas, in_axes=(0, None, None, 0)),
        [((1, 1 << 20, 784), F32), ((784, 1024), F32), ((1024,), F32),
         ((1, 1 << 20, 10), F32)],
    ),
    "stats_bf16": (
        elm_stats_pallas,
        [((4096, 784), BF16), ((784, 1024), BF16), ((1024,), F32),
         ((4096, 10), F32)],
    ),
    # four-chip phase: one node per chip, N_i=32768, L=2048
    "stats_f32_wide": (
        elm_stats_pallas,
        [((32768, 784), F32), ((784, 2048), F32), ((2048,), F32),
         ((32768, 10), F32)],
    ),
    "preact_stats_f32": (
        elm_preact_stats_pallas,
        [((4096, 1024), F32), ((1024,), F32), ((4096, 10), F32)],
    ),
    "predict_f32": (
        elm_predict_pallas,
        [((1024, 784), F32), ((784, 1024), F32), ((1024,), F32),
         ((1024, 10), F32)],
    ),
    # tenants phase: 64 tenants mixed in one flush
    "predict_stacked_T64": (
        elm_predict_stacked_pallas,
        [((1024, 784), F32), ((784, 1024), F32), ((1024,), F32),
         ((64, 1024, 10), F32), ((1024,), I32)],
    ),
    # neighbor-gossip phase: V=1024 hypercube (d=10), L=128, M=8
    "gossip_round_v1024": (_round(1024, 128, 8), _gossip_args(1024, 128, 8, 10)),
    "gossip_round_bf16_v1024": (
        _round(1024, 128, 8, compress="bf16"),
        _gossip_args(1024, 128, 8, 10),
    ),
    # the widest per-round state: 64 nodes at L=1024, M=10
    "gossip_round_v64_l1024": (_round(64, 1024, 10), _gossip_args(64, 1024, 10, 12)),
    # the rgg1024 deployment: V=1024, L=256, M=10, d_max 22 (block of 8)
    "gossip_round_rgg1024": (
        _round(1024, 256, 10), _gossip_args(1024, 256, 10, 22)
    ),
    "gossip_multiround_v16": (
        lambda *a: elm_gossip.elm_gossip_pallas_multiround(*a, num_rounds=5),
        _gossip_args(16, 128, 8, 4, S=2),
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, args = CASES[name]
    shapes = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in args
    ]
    compiled = jax.jit(kernel).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


#: the instruction name of each kernel's launches, which a device trace
#: shows (bench/metrics/stats_roofline.learn.py matches "elm_stats_pallas";
#: the stats kernel's whole-L arm adds "_whole_l")
KERNEL_NAMES = {
    "stats_f32_nodes64": "elm_stats_pallas",
    "stats_f32_mnist64": "elm_stats_pallas_whole_l",
    "stats_f32_silo": "elm_stats_pallas_whole_l",
    "preact_stats_f32": "elm_preact_stats_pallas",
    "predict_f32": "elm_predict_pallas",
    "predict_stacked_T64": "elm_predict_stacked_pallas",
    "gossip_round_v1024": "elm_gossip_pallas",
    "gossip_multiround_v16": "elm_gossip_pallas_multiround",
}
_CUSTOM_CALL = re.compile(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("name", sorted(KERNEL_NAMES))
def test_kernel_instruction_name(one_chip, name):
    kernel, args = CASES[name]
    shapes = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in args
    ]
    text = jax.jit(kernel).lower(*shapes).compile().as_text()
    names = _CUSTOM_CALL.findall(text)
    assert names and all(KERNEL_NAMES[name] in n for n in names), names
