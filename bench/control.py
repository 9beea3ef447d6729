"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 -m bench.control --workload <cell> --seconds <s> \
        --seeds 1 2 ... --control-seeds 21 22 23

For each of ``--seeds`` the cell is set up, drives a short window, and
compares what its timed path produced with the reference: the lower
readings. For each of ``--control-seeds`` the reference itself, computed
a precision step lower (three bf16 passes in place of float32 at
HIGHEST), takes the program's place in the same comparison: the upper
readings. Every seed runs in this one process, one after another, and
prints one JSON line. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402


def readings(cell: str, seeds, control_seeds, seconds: float, *,
             require_tpu: bool = True, manifest=None, bench_dir=harness.BENCH):
    spec = harness.resolve(manifest or harness.load_manifest(), cell, bench_dir)
    if require_tpu:
        devices = harness.accelerator(spec.chips)
        harness.use_compile_cache()
    else:
        import jax

        devices = jax.devices()[: spec.chips]
    module = harness.load_driver(spec.traffic["kind"])
    out = []
    for seed, control in [(s, False) for s in seeds] + [(s, True) for s in control_seeds]:
        driver = module.Driver(spec.config, spec.traffic, seed, devices)
        driver.setup()
        window = driver.window(seconds)
        driver.release()
        got = driver.control_outputs() if control else driver.outputs()
        line = {
            "cell": cell, "seed": seed, "control": control,
            "failed": window.failed, "readings": driver.compare(got),
        }
        print(json.dumps(line), flush=True)
        out.append(line)
        del driver, got
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    try:
        readings(args.workload, args.seeds, args.control_seeds, args.seconds)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
