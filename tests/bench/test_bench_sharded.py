"""A learning cell on the sharded engine (one node a device, ring
gossip through ppermute) runs from a new config file alone: four host
devices in a child process, tiny sizes."""

import json
import subprocess
import sys

from bench_tiny import REPO, cpu_env, tiny_bench  # noqa: F401

CHILD = r"""
import json, sys, time
from pathlib import Path
sys.path[:0] = [{repo!r}, {src!r}]
import jax
from bench import harness
assert len(jax.devices()) == 4
manifest = json.loads(Path({manifest!r}).read_text())
result = harness.run_cell(
    "silo4.learn", 5, 0.3, False, t_process=time.perf_counter(),
    manifest=manifest, bench_dir=Path({bench!r}), require_tpu=False,
)
print("RESULT", json.dumps(result))
"""


def test_sharded_learn_cell_on_four_devices(tiny_bench, tmp_path):
    manifest, bench_dir = tiny_bench
    cfg = json.loads((bench_dir / "configs" / "mnist64.json").read_text())
    cfg.update(name="silo4", V=4, Ni=128, engine="sharded",
               graph={"kind": "ring", "seed": 0})
    (bench_dir / "configs" / "silo4.json").write_text(json.dumps(cfg))
    (bench_dir / "limits" / "silo4.learn.json").write_text(
        (bench_dir / "limits" / "mnist64.learn.json").read_text()
    )
    manifest["configs"].append({
        "name": "silo4", "source": "https://arxiv.org/abs/1504.00981",
        "file": "bench/configs/silo4.json", "reduced": [], "why": "a test",
    })
    manifest["workloads"].append({
        "name": "silo4.learn", "config": "silo4", "traffic": "learn",
        "chips": 4, "why": "a test",
    })
    for m in manifest["end_to_end"]:
        if m["name"] == "learn_s":
            m["workloads"].append("silo4.learn")
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    code = CHILD.format(repo=str(REPO), src=str(REPO / "src"),
                        manifest=str(path), bench=str(bench_dir))
    env = dict(cpu_env(), XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(x for x in proc.stdout.splitlines() if x.startswith("RESULT "))
    result = json.loads(line[len("RESULT "):])
    assert result["correct"] is True
    assert result["device"]["count"] == 4
