"""Dry-run core: lower + compile every (arch x shape x mesh) combination.

Imported by launch/dryrun.py (which force-creates the 512 placeholder
devices *before* importing this module — see the assignment contract)
and by the roofline benchmark driver.
"""

from __future__ import annotations

import dataclasses
import json
import time
import traceback

import jax

from repro.analysis.roofline import roofline_from_compiled
from repro.configs import INPUT_SHAPES, get as get_config
from repro.configs.base import ArchConfig, InputShape
from repro.distributed import sharding as shd
from repro.distributed.steps import make_serve_bundle, make_train_bundle, jit_train_step
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.optim import adamw


@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    skipped: bool = False
    reason: str = ""
    act_shard: str = "none"
    lower_s: float = 0.0
    compile_s: float = 0.0
    memory: dict | None = None
    roofline: dict | None = None

    def as_dict(self):
        d = dataclasses.asdict(self)
        return d


def _memory_dict(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {}
    for f in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        out[f] = int(getattr(m, f, 0))
    out["peak_bytes_per_chip"] = (
        out["argument_size_in_bytes"]
        + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"]
        - out["alias_size_in_bytes"]
    )
    return out


def _lower_train(cfg: ArchConfig, shape: InputShape, mesh, microbatches: int = 1):
    bundle = make_train_bundle(cfg, mesh, adamw(3e-4), microbatches=microbatches)
    state_shape = jax.eval_shape(bundle.init_fn, jax.random.key(0))
    batch_shape = specs_lib.train_batch_specs(cfg, shape, bundle.node_count)
    step = jit_train_step(bundle, mesh, batch_shape)
    return step.lower(state_shape, batch_shape)


def _lower_prefill(cfg: ArchConfig, shape: InputShape, mesh):
    bundle = make_serve_bundle(
        cfg, mesh, batch=shape.global_batch, max_seq=shape.seq_len
    )
    params_shape = specs_lib.params_specs(cfg)
    batch_shape = specs_lib.prefill_batch_specs(cfg, shape)
    bspecs = bundle.batch_pspec_fn(batch_shape)
    bsh = shd.shardings(mesh, bspecs)
    fn = jax.jit(
        bundle.prefill_fn,
        in_shardings=(bundle.param_shardings, bsh),
        out_shardings=(None, bundle.cache_shardings),
    )
    return fn.lower(params_shape, batch_shape)


def _lower_decode(cfg: ArchConfig, shape: InputShape, mesh):
    bundle = make_serve_bundle(
        cfg, mesh, batch=shape.global_batch, max_seq=shape.seq_len
    )
    params_shape = specs_lib.params_specs(cfg)
    cache_shape, tok_shape = specs_lib.decode_specs(cfg, shape)
    tok_specs = bundle.batch_pspec_fn(tok_shape)
    tok_sh = shd.shardings(mesh, tok_specs)
    fn = jax.jit(
        bundle.decode_fn,
        in_shardings=(
            bundle.param_shardings,
            bundle.cache_shardings,
            tok_sh,
        ),
        out_shardings=(None, bundle.cache_shardings),
        donate_argnums=(1,),
    )
    return fn.lower(params_shape, cache_shape, tok_shape)


def run_combo(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    want_roofline: bool = True,
    act_shard: str | None = None,
    remat: bool | None = None,
    microbatches: int = 1,
) -> DryrunResult:
    cfg = get_config(arch)
    if act_shard is not None:
        cfg = dataclasses.replace(cfg, act_shard=act_shard)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    ok_app, reason = specs_lib.applicable(cfg, shape)
    if not ok_app:
        return DryrunResult(
            arch=arch, shape=shape_name, mesh=mesh_name,
            ok=True, skipped=True, reason=reason,
        )
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = mesh.devices.size
        t0 = time.time()
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                lowered = _lower_train(cfg, shape, mesh, microbatches)
            elif shape.kind == "prefill":
                lowered = _lower_prefill(cfg, shape, mesh)
            else:
                lowered = _lower_decode(cfg, shape, mesh)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        res = DryrunResult(
            arch=arch, shape=shape_name, mesh=mesh_name, ok=True,
            act_shard=cfg.act_shard,
            lower_s=t1 - t0, compile_s=t2 - t1,
            memory=_memory_dict(compiled),
        )
        if want_roofline:
            terms = roofline_from_compiled(
                compiled, cfg=cfg, shape=shape, mesh_name=mesh_name,
                chips=chips,
            )
            res.roofline = terms.as_dict()
        return res
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        return DryrunResult(
            arch=arch, shape=shape_name, mesh=mesh_name, ok=False,
            reason=f"{type(e).__name__}: {e}\n{traceback.format_exc()[-2000:]}",
        )


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON result here")
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument(
        "--act-shard", default=None, choices=["none", "batch", "seq"],
        help="activation-sharding override (perf experiments)",
    )
    ap.add_argument(
        "--no-remat", action="store_true",
        help="disable activation checkpointing (perf experiments)",
    )
    ap.add_argument(
        "--microbatches", type=int, default=1,
        help="gradient-accumulation splits of the per-node batch",
    )
    args = ap.parse_args(argv)

    res = run_combo(
        args.arch, args.shape, multi_pod=args.multi_pod,
        act_shard=args.act_shard,
        remat=False if args.no_remat else None,
        microbatches=args.microbatches,
    )
    payload = json.dumps(res.as_dict(), indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(payload)
    if not args.quiet:
        print(payload)
    if not res.ok:
        raise SystemExit(1)
