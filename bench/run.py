"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and under ``--trace 1`` the
``breakdown``); each number the correctness check compares is printed
with its limit, last on standard error and last in that object.
Without a TPU, with fewer chips than the cell asks for, or on a device
kind that ``bench/peaks.json`` does not list, it exits non-zero and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    try:
        return harness.main(args, t_start=T_START, root=ROOT)
    except harness.HarnessError as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
