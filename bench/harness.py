"""Finds a cell's files by name, runs it once, prints its result.

A run is: set-up (device data from the seed, warm-up of this cell's
shapes, compiles from the persistent cache), one measured window, the
device's peak memory, then the comparison with the plain reference.
The last line of standard output is the result object; the numbers
compared, each beside its limit, are the last lines of standard error.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
#: where traced runs leave their profiles (inside the checkout, ignored by git)
OUT = ROOT / ".bench_out"


class BenchError(Exception):
    """A run that cannot be made: no result is printed, exit code 2."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def applies(entry: dict, cell: str) -> bool:
    """A metric without ``workloads`` is reported by every cell."""
    return "workloads" not in entry or cell in entry["workloads"]


@dataclasses.dataclass(frozen=True)
class CellSpec:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def resolve(manifest: dict, cell: str, bench_dir: Path = BENCH) -> CellSpec:
    """Everything one cell needs, found by the names in the manifest."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if cell not in cells:
        raise BenchError(f"no workload {cell!r} in BENCHMARK.json")
    w = cells[cell]
    return CellSpec(
        name=cell,
        chips=int(w["chips"]),
        config=load_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench_dir / "limits" / f"{cell}.json"),
        end_to_end=[m for m in manifest["end_to_end"] if applies(m, cell)],
        per_layer=[m for m in manifest["per_layer"] if applies(m, cell)],
    )


def load_driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def load_reader(name: str, bench_dir: Path = BENCH):
    """``metrics/<name>.py``; names may hold dots, so load by path."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_peak(device_kind: str, bench_dir: Path = BENCH) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    peaks = load_json(bench_dir / "peaks.json")
    if device_kind not in peaks:
        raise BenchError(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"(known: {sorted(peaks)})"
        )
    return peaks[device_kind]


def accelerator(chips: int):
    """The first ``chips`` TPU devices; anything else is an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(
            f"no TPU found (platform {devices[0].platform!r}); the "
            "benchmark never falls back to another platform"
        )
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, found {len(devices)}")
    return devices[:chips]


def use_compile_cache(root: Path = ROOT) -> str:
    """Persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else a fixed directory in the checkout. Every program is cached,
    however fast it compiled, so that only a cell's first run compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs that XLA compiled (compile requests the
    persistent cache did not answer)."""

    def __init__(self):
        from jax import monitoring

        self.requests = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.requests - self.hits


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns."""

    end_to_end: dict  # metric name -> value
    counters: dict  # counts the per-layer readers use; printed earlier
    attempted: int
    failed: int
    seconds: float


@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader sees."""

    cell: str
    config: dict
    traffic: dict
    peak: dict
    chips: int
    counters: dict
    trace: object  # bench.trace.Trace


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def finite(x: float) -> float:
    """JSON has no infinity or NaN: a latency that never came, or a
    reading that could not be made, is 1e12."""
    return x if math.isfinite(x) else 1e12


def run_cell(
    cell: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_process: float,
    manifest: dict | None = None,
    bench_dir: Path = BENCH,
    require_tpu: bool = True,
) -> dict:
    """One run of one cell; returns the result object (also printed)."""
    import jax

    spec = resolve(manifest or load_manifest(), cell, bench_dir)
    if require_tpu:
        devices = accelerator(spec.chips)
        peak = load_peak(devices[0].device_kind, bench_dir)
        use_compile_cache()
    else:  # the harness's own tests, on the CPU
        devices = jax.devices()[: spec.chips]
        peak = None
    counter = CompileCounter()
    driver = load_driver(spec.traffic["kind"]).Driver(
        spec.config, spec.traffic, seed, devices
    )
    driver.setup()
    setup_s = time.perf_counter() - t_process
    compiled_setup, hits_setup = counter.compiled, counter.hits

    tracer = None
    if trace:
        from bench import trace as trace_lib

        tracer = trace_lib.Recorder(OUT / "trace" / cell)
        tracer.start()
    with span("window"):
        window = driver.window(seconds)
    summary = tracer.stop() if tracer is not None else None
    compiled_window = counter.compiled - compiled_setup
    mem = memory_peak(devices)
    driver.release()
    readings = driver.check()

    checks = {
        name: {"value": finite(readings.get(name, math.inf)), "limit": limit}
        for name, limit in spec.limits.items()
    }
    checks["failed"] = {"value": window.failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    dev = devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": mem,
    }
    print(json.dumps({
        "info": "run", "cell": cell, "seed": seed, "trace": bool(trace),
        "device": device, "setup_s": setup_s,
        "compiled_in_setup": compiled_setup, "cache_hits_in_setup": hits_setup,
        "compiled_in_window": compiled_window, "window_s": window.seconds,
        "attempted": window.attempted, "failed": window.failed,
        "counters": window.counters, "readings": readings,
    }), flush=True)

    metrics = {}
    breakdown = None
    if trace:
        ctx = MetricContext(
            cell=cell, config=spec.config, traffic=spec.traffic, peak=peak,
            chips=len(devices), counters=window.counters, trace=summary,
        )
        for entry in spec.per_layer:
            value = load_reader(entry["name"], bench_dir).read(ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
    else:
        values = dict(window.end_to_end, setup_s=setup_s)
        for entry in spec.end_to_end:
            metrics[entry["name"]] = {
                "value": finite(values[entry["name"]]), "unit": entry["unit"],
            }
    result = {
        "correct": bool(correct),
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} <= {c['limit']!r} {verdict}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result
