"""Tiny copies of the benchmark's cells, for the harness's CPU tests.

The copies keep every file of ``bench/`` that a cell is found by (its
traffic, limits, metrics and peaks) and shrink only the configurations'
scale (nodes, rows, hidden units), keeping MNIST's widths, so that a
whole run takes seconds on the CPU.
"""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "mnist64": dict(V=8, Ni=64, L=64, K=10, reference_rows=256,
                    graph={"kind": "random_geometric", "radius": 0.6, "seed": 0}),
    "rgg1024": dict(V=16, Ni=64, L=64, K=10, reference_rows=256,
                    graph={"kind": "random_geometric", "radius": 0.45, "seed": 0}),
}
TINY_TRAFFIC = {
    "learn": dict(max_rounds=5000),
    "stream": dict(ring=4, max_rounds=20000),
    "serve": dict(buckets=[4, 16], rows_max=40, pool_rows=512, rate_per_s=40),
}


@pytest.fixture
def tiny_bench(tmp_path):
    """(manifest, bench_dir) of the benchmark at tiny sizes."""
    bench = REPO / "bench"
    for sub in ("traffic", "limits", "metrics"):
        shutil.copytree(bench / sub, tmp_path / sub)
    shutil.copy(bench / "peaks.json", tmp_path / "peaks.json")
    (tmp_path / "configs").mkdir()
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in manifest["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        cfg.update(TINY[c["name"]])
        (tmp_path / "configs" / f"{c['name']}.json").write_text(json.dumps(cfg))
    for kind, changes in TINY_TRAFFIC.items():
        path = tmp_path / "traffic" / f"{kind}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **changes}))
    return manifest, tmp_path


def run_tiny(manifest, bench_dir, cell, seed=7, seconds=0.3):
    """One whole run of a tiny cell on the CPU; returns its result."""
    import time

    from bench import harness

    return harness.run_cell(
        cell, seed, seconds, False, t_process=time.perf_counter(),
        manifest=manifest, bench_dir=bench_dir, require_tpu=False,
    )


def cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env
