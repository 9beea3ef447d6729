"""A streaming cell's ``correct``: sound runs pass, the control and each
fault the cell can have fail (tiny sizes, on the CPU)."""

import json

import pytest
from bench_tiny import run_tiny, tiny_bench  # noqa: F401
from test_bench_learn import _altered, _half_batch, _no_exchange, _unchanged

from bench import control

CELL = "rgg1024.stream"


def test_sound_run_is_correct(tiny_bench):
    manifest, bench_dir = tiny_bench
    result = run_tiny(manifest, bench_dir, CELL)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "chunk_s"}


def test_control_fails(tiny_bench):
    manifest, bench_dir = tiny_bench
    limits = json.loads((bench_dir / "limits" / f"{CELL}.json").read_text())
    (line,) = control.readings(
        CELL, [], [12], 0.2, require_tpu=False, manifest=manifest,
        bench_dir=bench_dir,
    )
    assert any(line["readings"][k] > v for k, v in limits.items())


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
