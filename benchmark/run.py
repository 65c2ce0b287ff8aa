"""``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and prints
one JSON result as the last line of standard output. See ``README.md``.
"""
import time

T_PROCESS = time.monotonic()       # before anything heavy is imported

import argparse  # noqa: E402
import sys  # noqa: E402

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
