"""BENCHMARK.json, and every file it names, found by name."""

import json
import re
import subprocess
import sys

import pytest
from bench_tiny import REPO, cpu_env

from bench import harness

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in MANIFEST[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    texts = [e["why"] for k in ("configs", "workloads") for e in MANIFEST[k]]
    texts += [m["layer"] for m in MANIFEST["per_layer"]]
    assert all(0 < len(t) <= 200 and "\n" not in t for t in texts)
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda e: e["name"])
def test_config_resolves_by_name(entry):
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    cfg = json.loads((REPO / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    for key in ("source", "reduced", "assumed", "deployment", "precision",
                "C", "activation", "feature_scale", "graph", "eps", "K"):
        assert key in cfg
    assert set(entry["reduced"]) == set(cfg["reduced"])


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_resolves_and_reports_what_it_must(cell):
    spec = harness.resolve(MANIFEST, cell)
    assert harness.load_driver(spec.traffic["kind"]).Driver
    e2e = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer
    for m in spec.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_reader(m["name"]).read)
    assert spec.limits


@pytest.mark.parametrize("entry", MANIFEST["per_layer"], ids=lambda e: e["name"])
def test_every_metric_names_known_cells(entry):
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert (REPO / "bench" / "metrics" / f"{entry['name']}.py").exists()


def test_peaks_refuse_an_unknown_device_kind():
    assert harness.load_peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.load_peak("TPU v9 imaginary")


def test_no_tpu_means_no_result(tmp_path):
    """On the CPU the run fails before set-up and prints no result."""
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "mnist64.learn",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""


def test_a_checkout_of_only_the_benchmark_fails(tmp_path):
    """Without the program the command exits non-zero, with no result."""
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in MANIFEST["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload",
         "mnist64.learn", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=cpu_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
