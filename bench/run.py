"""Run one benchmark cell once.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits 2, printing no result, when no TPU is found, when the cell needs
more chips than there are, or when the chip's kind has no entry in
``bench/peaks.json``.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))  # the system under test
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_process=T_PROCESS,
        )
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
