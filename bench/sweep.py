"""Find the serving knee once, on the chip: the highest offered rate the
server keeps up with.

    python3 -m bench.sweep --workload mnist64.serve --seconds 8 --rates 100 200 400

One set-up, then one window per rate (the traffic file's mix at that
rate), in the order given; each prints a JSON line with the tail
latency, the rate of requests completed and the generator's lateness.
The sweep stops after the first rate whose completed rate falls below
0.9 of the offered one: past the knee the queue only grows. The cell's traffic file
then states 0.8 of the knee as a fixed number; the benchmark never
searches for a rate itself.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.resolve(harness.load_manifest(), args.workload)
    devices = harness.accelerator(spec.chips)
    harness.use_compile_cache()
    driver = harness.load_driver(spec.traffic["kind"]).Driver(
        spec.config, spec.traffic, args.seed, devices
    )
    driver.setup()
    for rate in args.rates:
        driver.rate = rate
        w = driver.window(args.seconds)
        print(json.dumps({
            "rate_per_s": rate,
            "completed_per_s": (w.attempted - w.failed) / w.seconds,
            "window_s": w.seconds,
            **w.end_to_end, **w.counters,
        }), flush=True)
        if (w.attempted - w.failed) / w.seconds < 0.9 * rate:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
