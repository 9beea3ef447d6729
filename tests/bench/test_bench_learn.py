"""A learning cell's ``correct``: sound runs pass, the control and each
fault the cell can have fail (tiny sizes, on the CPU)."""

import json

import jax.numpy as jnp
import pytest
from bench_tiny import run_tiny, tiny_bench  # noqa: F401

from bench import control

CELL = "mnist64.learn"


def test_sound_run_is_correct(tiny_bench):
    manifest, bench_dir = tiny_bench
    result = run_tiny(manifest, bench_dir, CELL)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "learn_s"}
    assert list(result)[-1] == "checks"


def test_control_fails(tiny_bench):
    """The reference in the program's place, three bf16 passes."""
    manifest, bench_dir = tiny_bench
    limits = json.loads((bench_dir / "limits" / f"{CELL}.json").read_text())
    (line,) = control.readings(
        CELL, [], [11], 0.2, require_tpu=False, manifest=manifest,
        bench_dir=bench_dir,
    )
    assert any(line["readings"][k] > v for k, v in limits.items())


def _unchanged(self, x, aux, gamma, num_iters, **kw):
    return x, None


def _half_batch(real):
    def raw_moments(X, T, feature_map, **kw):
        n = X.shape[0] // 2
        P_, Q_ = real(X[:n], T[:n], feature_map, **kw)
        return 2.0 * P_, 2.0 * Q_

    return raw_moments


def _altered(real):
    def run(self, x, aux, gamma, num_iters, **kw):
        out, traces = real(self, x, aux, gamma, num_iters, **kw)
        return out + 1e-2, traces

    return run


def _no_exchange(self, x, k=0):
    return jnp.zeros_like(x)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange", "altered"])
def test_fault_fails(tiny_bench, monkeypatch, fault):
    from repro.core import engine, mixers, stats

    if fault == "unchanged":
        monkeypatch.setattr(engine.ConsensusEngine, "run", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(stats, "raw_moments", _half_batch(stats.raw_moments))
    elif fault == "no_exchange":
        monkeypatch.setattr(mixers.DenseMixer, "laplacian", _no_exchange)
        monkeypatch.setattr(mixers.NeighborMixer, "laplacian", _no_exchange)
        monkeypatch.setattr(mixers.NeighborMixer, "_fused_ok", lambda *a, **k: False)
    else:
        monkeypatch.setattr(engine.ConsensusEngine, "run", _altered(engine.ConsensusEngine.run))
    manifest, bench_dir = tiny_bench
    assert run_tiny(manifest, bench_dir, CELL)["correct"] is False


def test_a_new_config_runs_without_editing_any_file(tiny_bench):
    """A configuration is a new file and a new manifest entry."""
    manifest, bench_dir = tiny_bench
    cfg = json.loads((bench_dir / "configs" / "mnist64.json").read_text())
    cfg.update(name="ring6", V=6, graph={"kind": "ring", "seed": 0})
    (bench_dir / "configs" / "ring6.json").write_text(json.dumps(cfg))
    (bench_dir / "limits" / "ring6.learn.json").write_text(
        (bench_dir / "limits" / f"{CELL}.json").read_text()
    )
    manifest = json.loads(json.dumps(manifest))
    manifest["configs"].append({
        "name": "ring6", "source": "https://arxiv.org/abs/1504.00981",
        "file": "bench/configs/ring6.json", "reduced": [], "why": "a test",
    })
    manifest["workloads"].append({
        "name": "ring6.learn", "config": "ring6", "traffic": "learn",
        "chips": 1, "why": "a test",
    })
    manifest["end_to_end"] = [
        {**m, "workloads": m["workloads"] + ["ring6.learn"]}
        if m["name"] == "learn_s" else m
        for m in manifest["end_to_end"]
    ]
    result = run_tiny(manifest, bench_dir, "ring6.learn")
    assert result["correct"] is True
    assert "learn_s" in result["metrics"]


def test_a_window_keeps_one_jobs_arrays(tiny_bench, monkeypatch):
    """Every job's Omegas staying alive until the window closes would
    fill the chip: the window's results hold counts only, and the driver
    keeps the last job's arrays alone."""
    import jax

    from bench import loops

    results = []
    real = loops.back_to_back

    def spy(job, seconds, **kw):
        out, elapsed = real(job, seconds, **kw)
        results.extend(out)
        return out, elapsed

    monkeypatch.setattr(loops, "back_to_back", spy)
    manifest, bench_dir = tiny_bench
    assert run_tiny(manifest, bench_dir, CELL, seconds=0.5)["correct"] is True
    assert len(results) >= 2
    assert not any(
        isinstance(v, jax.Array) for r in results for v in r.values()
    )
