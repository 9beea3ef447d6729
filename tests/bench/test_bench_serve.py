"""A serving cell: its open loop, and its ``correct`` (tiny sizes, CPU)."""

import json

import numpy as np
import pytest
from bench_tiny import run_tiny, tiny_bench  # noqa: F401

from bench import control, harness

CELL = "mnist64.serve"


@pytest.fixture
def serve_bench(tiny_bench):
    """The tiny benchmark with the serving cell in its manifest (the
    harness runs it whether or not ``BENCHMARK.json`` lists it yet)."""
    manifest, bench_dir = tiny_bench
    if CELL not in {w["name"] for w in manifest["workloads"]}:
        manifest["workloads"].append({
            "name": CELL, "config": "mnist64", "traffic": "serve",
            "chips": 1, "why": "a test",
        })
        manifest["end_to_end"].append({
            "name": "serve_p99_ms", "unit": "ms", "better": "lower",
            "bound": 0.25, "source": "host_clock", "workloads": [CELL],
        })
    return manifest, bench_dir


def test_sound_run_is_correct(serve_bench):
    manifest, bench_dir = serve_bench
    result = run_tiny(manifest, bench_dir, CELL, seconds=0.5)
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["serve_p99_ms"]["value"] > 0


def test_latency_is_timed_from_the_due_time(serve_bench):
    """A server stalled 50 ms on its first flush makes every request due
    meanwhile at least that late, though each was submitted late too."""
    import time

    manifest, bench_dir = serve_bench
    spec = harness.resolve(manifest, CELL, bench_dir)
    import jax

    driver = harness.load_driver("serve").Driver(
        spec.config, spec.traffic, 3, jax.devices()[:1]
    )
    driver.setup()
    real = driver.server.flush
    stalled = []

    def flush():
        if not stalled:
            stalled.append(1)
            time.sleep(0.05)
        return real()

    driver.server.flush = flush
    w = driver.window(0.3)
    assert w.failed == 0
    assert w.end_to_end["serve_p99_ms"] >= 50.0
    assert w.counters["late_p99_ms"] >= 0.0


def test_schedule_is_the_same_work_for_every_seed(serve_bench):
    manifest, bench_dir = serve_bench
    spec = harness.resolve(manifest, CELL, bench_dir)
    mod = harness.load_driver("serve")
    drivers = [mod.Driver(spec.config, spec.traffic, s, [None]) for s in (1, 2)]
    scheds = []
    for d in drivers:
        d.rng = np.random.default_rng(d.seed)
        scheds.append(d.schedule(2.0))
    (due1, size1, _), (due2, size2, _) = scheds
    assert sorted(size1) == sorted(size2) and list(size1) != list(size2)
    assert np.isclose(due1[-1], due2[-1])


def test_control_and_an_altered_answer_fail(serve_bench, monkeypatch):
    manifest, bench_dir = serve_bench
    limits = json.loads((bench_dir / "limits" / f"{CELL}.json").read_text())
    (line,) = control.readings(
        CELL, [], [13], 0.3, require_tpu=False, manifest=manifest,
        bench_dir=bench_dir,
    )
    assert any(line["readings"][k] > v for k, v in limits.items())

    from repro.kernels import elm_predict_ops

    real = elm_predict_ops.predict_map

    def altered(x, fmap, beta, **kw):
        return real(x, fmap, beta, **kw) * (1.0 + 1e-3)

    monkeypatch.setattr(elm_predict_ops, "predict_map", altered)
    assert run_tiny(manifest, bench_dir, CELL, seconds=0.3)["correct"] is False
