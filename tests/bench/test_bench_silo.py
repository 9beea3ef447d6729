"""The ``mnist8m-silo4.learn`` cell: the committed configuration, with
only N_i and L shrunk, runs through the harness on four host devices
(one silo a device, ring rounds through ppermute) and is correct
against ``bench/reference.py`` at the cell's own limits."""

import json
import subprocess
import sys

from bench_tiny import REPO, cpu_env, tiny_bench  # noqa: F401

CELL = "mnist8m-silo4.learn"
CHILD = r"""
import json, sys, time
from pathlib import Path
sys.path[:0] = [{repo!r}, {src!r}]
import jax
from bench import harness
assert len(jax.devices()) == 4
result = harness.run_cell(
    {cell!r}, 11, 0.3, False, t_process=time.perf_counter(),
    manifest=json.loads(Path({manifest!r}).read_text()),
    bench_dir=Path({bench!r}), require_tpu=False,
)
print("RESULT", json.dumps(result))
"""


def test_committed_silo_config_only_shrinks_rows_and_width(tiny_bench):
    _, bench_dir = tiny_bench
    name = "mnist8m-silo4.json"
    committed = json.loads((REPO / "bench" / "configs" / name).read_text())
    tiny = json.loads((bench_dir / "configs" / name).read_text())
    assert {k for k in committed if committed[k] != tiny[k]} == {"Ni", "L"}
    assert (committed["V"], committed["engine"], committed["graph"]["kind"]) == (
        4, "sharded", "ring")


def test_silo_cell_is_correct_on_four_devices(tiny_bench, tmp_path):
    manifest, bench_dir = tiny_bench
    path = tmp_path / "manifest.json"
    path.write_text(json.dumps(manifest))
    code = CHILD.format(repo=str(REPO), src=str(REPO / "src"), cell=CELL,
                        manifest=str(path), bench=str(bench_dir))
    env = dict(cpu_env(), XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(x for x in proc.stdout.splitlines() if x.startswith("RESULT "))
    result = json.loads(line[len("RESULT "):])
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["checks"]) == {"q_rel", "qsum_rel", "zgs_rel", "failed"}
