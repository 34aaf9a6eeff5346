"""Fidelity spectrum: interchangeable execution backends.

gem5's CPU models span a fidelity/performance spectrum (§1.3.1 ②):
"simple" atomic models, detailed in-order/O3 timing models, and the
KVM-based model that executes natively.  The *same* system description
runs under any of them.

g5x reproduces this for a JAX step function.  A ``StepProgram`` (the
system under test: jitted step + input specs + shardings + mesh) can be
executed by:

* ``NativeBackend``   — really run it (gem5's KVM mode: host execution,
                        no timing model, fastest, real numbers).
* ``DryRunBackend``   — ``.lower().compile()`` only; produces the
                        compiled artifact, memory/cost analysis, and the
                        HLO text (gem5's "atomic" functional mode:
                        correct structure, no timing).
* ``DesimBackend``    — parse the compiled HLO into an elastic trace and
                        replay it on the discrete-event TPU machine
                        model (gem5's detailed timing mode).

All three return a ``StepReport`` so drivers and benchmarks can switch
fidelity with one flag — exactly how gem5 users swap CPU models.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax


@dataclass
class StepProgram:
    """The system under test, in gem5 terms: the workload + config."""

    name: str
    fn: Callable                      # the step function (pure)
    input_specs: Any                  # pytree of ShapeDtypeStruct
    in_shardings: Any = None
    out_shardings: Any = None
    mesh: Optional[jax.sharding.Mesh] = None
    donate_argnums: tuple = ()
    static_argnums: tuple = ()

    def jitted(self):
        kw: Dict[str, Any] = {}
        if self.in_shardings is not None:
            kw["in_shardings"] = self.in_shardings
        if self.out_shardings is not None:
            kw["out_shardings"] = self.out_shardings
        if self.donate_argnums:
            kw["donate_argnums"] = self.donate_argnums
        if self.static_argnums:
            kw["static_argnums"] = self.static_argnums
        return jax.jit(self.fn, **kw)

    def lower(self):
        # input_specs is a tuple of positional args; each arg may be a
        # pytree of ShapeDtypeStructs.
        if self.mesh is not None:
            with self.mesh:
                return self.jitted().lower(*self.input_specs)
        return self.jitted().lower(*self.input_specs)


@dataclass
class StepReport:
    backend: str
    name: str
    wall_s: float = 0.0                       # host wall time of the call
    predicted_step_s: Optional[float] = None  # desim/roofline prediction
    outputs: Any = None
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    collective_bytes: Optional[float] = None
    memory: Optional[Dict[str, float]] = None
    detail: Dict[str, Any] = field(default_factory=dict)


class Backend:
    kind = "abstract"

    def run(self, prog: StepProgram, *args, **kw) -> StepReport:
        raise NotImplementedError


class NativeBackend(Backend):
    """Execute for real (gem5 KVM mode)."""

    kind = "native"

    def run(self, prog: StepProgram, *args, iters: int = 1) -> StepReport:
        f = prog.jitted()
        ctx = prog.mesh or _nullcontext()
        with ctx:
            out = f(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = f(*args)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / max(iters, 1)
        return StepReport(self.kind, prog.name, wall_s=dt, outputs=out)


class DryRunBackend(Backend):
    """Lower + compile only; extract compiled-artifact analyses."""

    kind = "dryrun"

    def run(self, prog: StepProgram) -> StepReport:
        t0 = time.perf_counter()
        lowered = prog.lower()
        compiled = lowered.compile()
        dt = time.perf_counter() - t0
        rep = StepReport(self.kind, prog.name, wall_s=dt)
        ca = compiled.cost_analysis()
        ma = compiled.memory_analysis()
        rep.memory = {
            "argument_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": float(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": float(getattr(ma, "alias_size_in_bytes", 0)),
            "code_bytes": float(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
        rep.detail["compiled"] = compiled
        rep.detail["hlo"] = compiled.as_text()
        # loop-corrected analysis (XLA's cost_analysis counts scan
        # bodies once; see repro.core.desim.hlo_cost)
        from repro.core.desim.hlo_cost import analyze_hlo
        cost = analyze_hlo(rep.detail["hlo"])
        rep.flops = cost.flops
        rep.bytes_accessed = cost.bytes
        rep.collective_bytes = cost.collective_bytes
        rep.detail["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}
        return rep


class DesimBackend(Backend):
    """Discrete-event timing replay of the compiled step.

    Runs through the ``repro.sim.Simulator`` front-end (the gem5-stdlib
    layer), so the same run can be scripted with exit events,
    checkpointed, or sampled by driving a ``Simulator``/``Board``
    directly — this backend is the one-shot convenience path.
    ``board`` accepts a prebuilt ``repro.sim.boards.Board`` (or use
    ``machine=`` with a raw ClusterModel, as before).

    ``record_stats=True`` additionally dumps the run's gem5-style
    statistics tree (per-chip/per-wire/fabric counters) into
    ``report.detail["stats"]`` (flat dict) and
    ``report.detail["stats_text"]`` (gem5 stats.txt-style dump).

    ``workers=N`` (N>1) shards the machine's pods across N worker
    processes (dist-gem5 multiprocess simulation, ``repro.core.desim.
    parallel``) — same numbers, less wall clock on multipod boards.
    """

    kind = "desim"

    def __init__(self, machine=None, record_stats: bool = False,
                 board=None, workers: int = 1):
        # machine: repro.core.desim.machine.ClusterModel (built lazily)
        self.machine = machine
        self.board = board
        self.record_stats = record_stats
        self.workers = int(workers or 1)

    def run(self, prog: StepProgram,
            dryrun_report: Optional[StepReport] = None) -> StepReport:
        from repro.core.desim import machine as mc
        from repro.core.desim.trace import HloTrace
        from repro.sim import Board, Simulator

        if dryrun_report is None:
            dryrun_report = DryRunBackend().run(prog)
        board = self.board or Board(
            machine=self.machine or mc.default_cluster(prog.mesh))
        t0 = time.perf_counter()
        trace = HloTrace.from_hlo_text(
            dryrun_report.detail["hlo"], name=prog.name,
            total_flops=dryrun_report.flops or 0.0,
            total_bytes=dryrun_report.bytes_accessed or 0.0)
        sim = Simulator(board, trace, record_stats=self.record_stats,
                        workers=self.workers)
        result = sim.run_to_completion()
        dt = time.perf_counter() - t0
        rep = StepReport(self.kind, prog.name, wall_s=dt,
                         predicted_step_s=result.makespan_s,
                         flops=dryrun_report.flops,
                         bytes_accessed=dryrun_report.bytes_accessed,
                         collective_bytes=dryrun_report.collective_bytes,
                         memory=dryrun_report.memory)
        rep.detail["desim"] = result
        rep.detail["hlo"] = dryrun_report.detail["hlo"]
        if self.record_stats and sim.sim_root is not None:
            rep.detail["stats"] = result.stats
            rep.detail["stats_text"] = sim.sim_root.stats.dump_text()
        return rep


BACKENDS = {
    "native": NativeBackend,
    "dryrun": DryRunBackend,
    "desim": DesimBackend,
}


def get_backend(kind: str, **kw) -> Backend:
    try:
        return BACKENDS[kind](**kw)
    except KeyError:
        raise ValueError(f"unknown backend {kind!r}; one of {list(BACKENDS)}")


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
