"""Chip benchmark: run one cell of ``BENCHMARK.json`` and print its line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  With ``--trace 0`` the last line of stdout carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of part of the window.  With no TPU, a device that
is not in ``chipbench/peaks.json``, or fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

import time

STARTED = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from chipbench import harness
    harness.main(ROOT, args.workload, args.seed, args.seconds,
                 bool(args.trace), started=STARTED)


if __name__ == "__main__":
    main()
