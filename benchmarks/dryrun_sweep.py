"""Sweep the full (arch x shape x mesh) dry-run matrix.

Each combo runs in its own subprocess (fresh XLA with 512 placeholder
devices); results land in benchmarks/results/dryrun/*.json and the
aggregate table in benchmarks/results/dryrun_table.json.

Usage:
  PYTHONPATH=src python -m benchmarks.dryrun_sweep [--only arch[,arch]]
      [--shapes s1,s2] [--meshes single,multi] [--force]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results", "dryrun")

ARCHS = [
    "grok-1-314b",
    "qwen2-72b",
    "starcoder2-3b",
    "internvl2-2b",
    "mamba2-780m",
    "h2o-danube-1.8b",
    "dbrx-132b",
    "musicgen-large",
    "gemma2-2b",
    "zamba2-1.2b",
]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
MESHES = {"single": [], "multi": ["--multi-pod"]}


def run_one(arch: str, shape: str, mesh: str, force: bool) -> dict:
    tag = f"{arch}_{shape}_{mesh}".replace("/", "-")
    out = os.path.join(RESULTS_DIR, tag + ".json")
    if os.path.exists(out) and not force:
        with open(out) as f:
            return json.load(f)
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--out", out, "--quiet",
        *MESHES[mesh],
    ]
    t0 = time.time()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # placeholder host devices, never the chip
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=1800
    )
    if not os.path.exists(out):
        rec = {
            "arch": arch, "shape": shape,
            "mesh": "2x16x16" if mesh == "multi" else "16x16",
            "ok": False, "skipped": False,
            "reason": f"subprocess rc={proc.returncode}: "
            + proc.stderr[-1500:],
            "wall_s": time.time() - t0,
        }
        with open(out, "w") as f:
            json.dump(rec, f, indent=2)
        return rec
    with open(out) as f:
        rec = json.load(f)
    rec["wall_s"] = time.time() - t0
    with open(out, "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(RESULTS_DIR, exist_ok=True)
    archs = args.only.split(",") if args.only else ARCHS
    shapes = args.shapes.split(",")
    meshes = args.meshes.split(",")

    rows = []
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                rec = run_one(arch, shape, mesh, args.force)
                rows.append(rec)
                status = (
                    "SKIP" if rec.get("skipped")
                    else ("OK" if rec.get("ok") else "FAIL")
                )
                extra = ""
                if rec.get("roofline"):
                    r = rec["roofline"]
                    extra = (
                        f" bottleneck={r['bottleneck']}"
                        f" t=({r['t_compute_s']:.3g},{r['t_memory_s']:.3g},"
                        f"{r['t_collective_s']:.3g})s"
                        f" peak={rec['memory']['peak_bytes_per_chip']/2**30:.1f}GiB"
                    )
                print(
                    f"[{status}] {arch:18s} {shape:12s} {mesh:6s}"
                    f" wall={rec.get('wall_s', 0):.0f}s{extra}",
                    flush=True,
                )
    table = os.path.join(os.path.dirname(RESULTS_DIR), "dryrun_table.json")
    with open(table, "w") as f:
        json.dump(rows, f, indent=2)
    n_ok = sum(r.get("ok", False) for r in rows)
    n_skip = sum(r.get("skipped", False) for r in rows)
    n_fail = sum(
        (not r.get("ok", False)) and (not r.get("skipped", False))
        for r in rows
    )
    print(f"\n{n_ok} ok ({n_skip} skips) / {n_fail} FAILED of {len(rows)}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
