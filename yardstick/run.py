#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 yardstick/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; the whole
of it is also written to yardstick/out/<workload>.<seed>.json. Exit code 3
and no result where jax finds no TPU (or fewer chips than the cell asks
for), 4 where set-up failed. See README.md.
"""

import time

T_START = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main() -> int:
    # the script's own directory leaves the path, the checkout's root
    # enters it: ``yardstick`` and the program import as packages, here and
    # in the processes spawned from here
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    from yardstick import harness
    return harness.run(ap.parse_args(), T_START)


if __name__ == "__main__":
    sys.exit(main())
