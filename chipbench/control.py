"""Readings for the limits of ``correct``, on the chip at a cell's size.

    python3 chipbench/control.py --workload <name> --seconds <s> --seed <n>

A whole run of the cell (set-up, the window, the comparison with the
reference) and then the same comparison for what must fail it, each
against the reference:

- ``fp8``: the control, the reference put in the program's place at
  float8 (e4m3, one scale per tensor), the precision below the bfloat16
  the configurations state;
- ``half_batch`` (training): the reference's steps over half of each
  batch, the mean taken over that half.

One JSON line: the program's numbers (its lower readings) and each of
these (the upper).  Run it once per seed: a process that serves a second
set of weights can run out of device memory.  The benchmark's own runs
never do this.
"""

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    from chipbench import harness
    bench = harness.Bench(ROOT)
    cell = bench.cell(args.workload)
    devices, peak = harness.check_devices(int(cell["chips"]))
    harness.use_compile_cache(ROOT)
    ctx = harness.Context(bench, cell, args.seed, args.seconds, False,
                          devices, peak, harness.CompileLog(), STARTED)
    ctx.control = True
    out = bench.driver(ctx.traffic["driver"]).run(ctx)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "program": {k: v for k, (v, _) in out["checks"].items()},
        "limits": {k: lim for k, (_, lim) in out["checks"].items()},
        **out["control"], "e2e": out["e2e"], "notes": out["notes"]}))


if __name__ == "__main__":
    main()
