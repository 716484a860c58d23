"""Read a cell's correctness numbers for its control on several seeds
in one process (the benchmark's own runs never run the control; they
give the program's readings).

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

The control is the configuration's ``control`` entry: the program's own
lower-precision path (``"kind": "program"``) or the reference at the
lower precision put in the program's place (``"kind": "reference"``).
One JSON line per seed: the workload, the seed, ``correct`` and the
compared numbers.  A sound limit lies above every program reading and
below every control reading.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    try:
        device, peak = harness.open_chip(ROOT, 1)
    except harness.HarnessError as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 2
    for seed in args.seeds:
        r = harness.run_cell(ROOT, args.workload, seed, args.seconds, False,
                             peak=peak, t_start=time.perf_counter(),
                             control=True, device=device)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": True, "correct": r["correct"],
                          "checks": r["checks"], "window": r["window"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
