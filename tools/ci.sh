#!/usr/bin/env bash
# Time-bounded tier-1 verification: the full suite minus the
# jit-compiling model smokes (marked `slow`), so a CI lap finishes in
# well under a minute instead of ~3 minutes of XLA compile time.
#
#   tools/ci.sh              # fast subset (default: -m "not slow")
#   CI_MARKER="" tools/ci.sh # everything
#   tools/ci.sh -k executor  # extra pytest args pass through
#   tools/ci.sh smoke        # example + benchmark bit-rot tier: runs
#                            # examples/quickstart.py, the serving smoke
#                            # lap (examples/serve_sim.py: short Poisson
#                            # run, asserts nonzero goodput + stats), and
#                            # `python -m benchmarks.run --json fidelity`
#                            # (writes BENCH_desim.json)
#   tools/ci.sh golden       # gem5-style golden-stats regression tier:
#                            # diffs live stats dumps of the canonical
#                            # board x trace runs against the committed
#                            # tests/golden/*.txt (regen with
#                            # `pytest tests/test_golden_stats.py
#                            #  --regen-golden`, then review + commit)
#   tools/ci.sh perf         # perf-smoke tier: asserts AtomicTiming is
#                            # >= 3x faster wall-clock than Detailed-
#                            # Timing on the pod_torus reference trace
#                            # (and tick-exact there) — fails loudly if
#                            # the fast path regresses
#   tools/ci.sh parallel     # parallel-smoke tier: asserts the multi-
#                            # process ParallelEngine (workers=4) is
#                            # >= 2x faster wall-clock than the serial
#                            # TraceExecutor on the 32-pod reference
#                            # workload AND bit-exact (full ExecResult
#                            # + stats-tree equality) across two laps
#                            # of one warm worker pool — then the
#                            # fleet gate: workers=8 on the 64-pod
#                            # v5e_fleet_big board >= 4x serial, bit-
#                            # exact, with barriers bounded by the DCN
#                            # collective count (lookahead elision)
#   tools/ci.sh fleet        # autoscaled-serving tier: the flash-crowd
#                            # lap (benchmarks/fleet_sweep.py
#                            # --assert-fleet) — asserts the autoscaler
#                            # scales up, post-crowd SLO compliance
#                            # recovers (and provably does not on the
#                            # fixed-size fleet), and the lap is bit-
#                            # identical across two runs — plus the
#                            # examples/fleet_sim.py demo with its
#                            # DES-vs-controller replay identity check
#   tools/ci.sh simpoint     # sampling-accuracy tier: the bursty
#                            # reference workload (benchmarks/
#                            # simpoint_sweep.py --assert-simpoint) —
#                            # asserts the SimPoint-weighted
#                            # reconstruction AND the checkpoint-library
#                            # fanout land within 5% of the full-detail
#                            # total while the equal-budget fixed-stride
#                            # plan misses by more
#   tools/ci.sh trace        # observability tier: fully-instrumented
#                            # smoke lap (m5out stats.txt/config.json +
#                            # Perfetto trace, serial and workers=4),
#                            # validates the trace-event JSON schema,
#                            # asserts bit-identity with the bare lap
#                            # and < 5% flags-disabled DPRINTF overhead
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# every tier runs on the CPU, also on a machine with a TPU: the chip is
# reached only through chip_smoke.py
export JAX_PLATFORMS=cpu
if [ "${1-}" = "golden" ]; then
  shift
  python -m pytest -q tests/test_golden_stats.py "$@"
  echo "golden tier OK"
  exit 0
fi
if [ "${1-}" = "perf" ]; then
  shift
  python -m benchmarks.engine_microbench --assert-speedup 3
  echo "perf tier OK"
  exit 0
fi
if [ "${1-}" = "parallel" ]; then
  shift
  python -m benchmarks.distgem5_scaling --assert-parallel 2
  python -m benchmarks.distgem5_scaling --assert-parallel-big 4
  echo "parallel tier OK"
  exit 0
fi
if [ "${1-}" = "simpoint" ]; then
  shift
  python -m benchmarks.simpoint_sweep --assert-simpoint
  echo "simpoint tier OK"
  exit 0
fi
if [ "${1-}" = "trace" ]; then
  shift
  python -m benchmarks.observability --assert-overhead 5
  echo "trace tier OK"
  exit 0
fi
if [ "${1-}" = "fleet" ]; then
  shift
  python -m benchmarks.fleet_sweep --assert-fleet
  python examples/fleet_sim.py
  echo "fleet tier OK"
  exit 0
fi
if [ "${1-}" = "smoke" ]; then
  shift
  python examples/quickstart.py
  python examples/serve_sim.py
  python -m benchmarks.run --json fidelity
  echo "smoke tier OK"
  exit 0
fi
MARKER=${CI_MARKER-"not slow"}
if [ -n "$MARKER" ]; then
  exec python -m pytest -q -m "$MARKER" "$@"
fi
exec python -m pytest -q "$@"
