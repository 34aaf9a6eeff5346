"""Trainer: the driver loop as a SimObject (gem5-style composition).

The trainer is configured like every other g5x component — Params +
children (checkpoint manager, watchdog, heartbeat) — and exports a
stats group (loss, step-time distribution, straggler count, checkpoint
count) into the system tree.  Fault injection for tests: pass
``fail_at={step: exception}`` and the trainer demonstrates
checkpoint-restore recovery.

The batch of the next step is built ahead, on one worker thread, while
the current step runs on the device: once step k is dispatched, the
worker runs ``pipeline.batch(k + 1)`` and puts it on the device.  Step
k + 1 takes that batch if the step it is about to run is the one the
worker built, and otherwise (the first step, a restore or rewind)
builds its own as before.  ``batch(step)`` is a pure function of
``(seed, step)``, so the bits are the same either way.  The prefetch
lives across calls to ``run``; an exception in the worker is raised by
the step that takes its batch, and a batch no step takes is dropped.

Each step is marked with ``jax.profiler`` spans, recorded only while a
profiler session is active (``jax.profiler.trace``), on the host thread
that runs the step:

- ``train.step``: the whole step, as the profiler's step
  (``step_num`` = the step);
- ``train.input``: waiting for the step's batch on the device: for the
  worker's, or building it here where the worker built another step's;
- ``train.dispatch``: the call of the jitted step (it runs
  asynchronously);
- ``train.sync``: waiting for the step's loss to reach the host.

and on the worker's thread:

- ``train.prefetch``: building a batch ahead and putting it on the
  device (``step_num`` = the step it is for).

The stat ``input_time`` (and each history row's ``input_s``) holds the
seconds spent in ``train.input``, whether or not a profiler runs;
``input_prefetched`` counts the steps whose batch the worker built
(each history row's ``prefetched``).
"""

from __future__ import annotations

import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.checkpoint import CheckpointManager
from repro.core.simobject import Param, SimObject
from repro.data.pipeline import SyntheticPipeline
from repro.train.ft import Heartbeat, StragglerWatchdog
from repro.train.ft_policy import (FailureSchedule, FTPolicy,
                                   checkpoint_due)

SPAN_STEP = "train.step"
SPAN_INPUT = "train.input"
SPAN_DISPATCH = "train.dispatch"
SPAN_SYNC = "train.sync"
SPAN_PREFETCH = "train.prefetch"


class SimulatedFailure(RuntimeError):
    pass


class Trainer(SimObject):
    ckpt_interval = Param(int, 50, "steps between checkpoints")
    log_interval = Param(int, 10, "steps between metric logs")
    max_retries = Param(int, 3, "restore attempts after failures")

    def __init__(self, name: str = "trainer", *, model, train_step: Callable,
                 pipeline: SyntheticPipeline, state: Any,
                 ckpt_dir: Optional[str] = None,
                 heartbeat_path: Optional[str] = None, **kw):
        super().__init__(name, **kw)
        self.model = model
        self.train_step = train_step
        self.pipeline = pipeline
        self.state = state
        self.ckpt = (CheckpointManager(ckpt_dir) if ckpt_dir else None)
        self.watchdog = StragglerWatchdog()
        self.heartbeat = Heartbeat(heartbeat_path) if heartbeat_path else None
        self._jitted = jax.jit(train_step, donate_argnums=(0,))
        # builds the next step's batch; its thread starts on the first
        # prefetch and ends once the trainer is collected
        self._worker = ThreadPoolExecutor(
            1, thread_name_prefix="trainer-prefetch")
        self._ahead: Optional[Tuple[int, Future]] = None
        # stats
        self.s_loss = self.stats.scalar("loss", "last loss")
        self.s_steps = self.stats.scalar("steps", "steps completed")
        self.s_failures = self.stats.scalar("failures", "failures recovered")
        self.s_stragglers = self.stats.scalar("stragglers", "slow steps")
        self.s_stalls = self.stats.scalar("stalls",
                                          "attempts hung on a silent pod")
        self.s_step_time = self.stats.distribution("step_time", unit="s")
        self.s_input_time = self.stats.distribution(
            "input_time", "waiting for a step's batch on the device",
            unit="s")
        self.s_prefetched = self.stats.scalar(
            "input_prefetched", "steps whose batch the worker built ahead")
        self.history: list = []

    # ------------------------------------------------------------------
    def _build_batch(self, step: int) -> Dict[str, jax.Array]:
        return {k: jax.numpy.asarray(v)
                for k, v in self.pipeline.batch(step).items()}

    def _prefetch_batch(self, step: int) -> Dict[str, jax.Array]:
        with jax.profiler.TraceAnnotation(SPAN_PREFETCH, step_num=step):
            return self._build_batch(step)

    def _take_batch(self, step: int) -> Tuple[Dict[str, jax.Array], bool]:
        """``step``'s batch, and whether the worker built it: the
        worker's if it built this step's (raising what it raised), else
        one built here."""
        ahead = self._ahead
        if ahead is not None and ahead[0] == step:
            self._ahead = None
            return ahead[1].result(), True
        return self._build_batch(step), False

    def _prefetch(self, step: int) -> None:
        """Start building ``step``'s batch on the worker, unless it is
        still building one that no step took: at most one batch is ever
        in flight."""
        ahead = self._ahead
        if ahead is not None and not (ahead[1].done() or ahead[1].cancel()):
            return
        self._ahead = (step, self._worker.submit(self._prefetch_batch, step))

    def _run_one_step(self, step: int) -> None:
        """One real training step with all its bookkeeping (stats,
        watchdog, history, heartbeat) — the single copy both ``run``
        and ``run_ft`` execute."""
        with jax.profiler.StepTraceAnnotation(SPAN_STEP, step_num=step):
            with jax.profiler.TraceAnnotation(SPAN_INPUT):
                t_in = time.perf_counter()
                batch, prefetched = self._take_batch(step)
                input_s = time.perf_counter() - t_in
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
                self.state, metrics = self._jitted(self.state, batch)
            self._prefetch(step + 1)
            with jax.profiler.TraceAnnotation(SPAN_SYNC):
                loss = float(jax.device_get(metrics["loss"]))
            dt = time.perf_counter() - t0
            if self.watchdog.record(step, dt):
                self.s_stragglers.inc()
            self.s_step_time.sample(dt)
            self.s_input_time.sample(input_s)
            if prefetched:
                self.s_prefetched.inc()
            self.s_loss.set(loss)
            self.s_steps.inc()
            self.history.append({"step": step, "loss": loss, "time_s": dt,
                                 "input_s": input_s,
                                 "prefetched": prefetched})
            if self.heartbeat:
                self.heartbeat.beat(step)

    def run(self, num_steps: int,
            fail_at: Optional[Dict[int, Exception]] = None) -> Dict:
        """Run ``num_steps``; simulated failures trigger restore+retry."""
        fail_at = dict(fail_at or {})
        retries = 0
        step = int(jax.device_get(self.state["step"]))
        end = step + num_steps
        while step < end:
            try:
                if step in fail_at:
                    exc = fail_at.pop(step)
                    raise exc
                self._run_one_step(step)
                step += 1
                if self.ckpt and checkpoint_due(step, self.ckpt_interval):
                    self.ckpt.save(self.state, step)
            except SimulatedFailure:
                self.s_failures.inc()
                retries += 1
                if retries > self.max_retries:
                    raise
                if self.ckpt:
                    self.ckpt.wait()    # a save still being written counts
                    if self.ckpt.latest_step() is not None:
                        self.state = self.ckpt.restore(self.state)
                        step = int(jax.device_get(self.state["step"]))
                # else: continue from in-memory state (lost step)
        if self.ckpt:
            self.ckpt.save(self.state, step)
            self.ckpt.wait()
        return {"final_step": step, "history": self.history,
                "stragglers": self.watchdog.flagged}

    # ------------------------------------------------------------------
    def run_ft(self, schedule: FailureSchedule, policy: FTPolicy) -> Dict:
        """Run under a seeded :class:`FailureSchedule` with every
        recovery decision delegated to the pure :class:`FTPolicy` — the
        identical policy object the DES ``repro.sim.workloads.TrainSim``
        drives, so the two produce the same decision log on the same
        schedule (tests/test_train_ft_policy.py).

        The trainer owns the side effects: it really runs the jitted
        steps, really writes checkpoints through
        :class:`CheckpointManager`, and on a declared pod death really
        restores the policy's chosen checkpoint (onto the policy's
        elastic mesh at pod scale; on this host the restore itself).
        """
        if self.ckpt is None:
            raise ValueError("run_ft requires a CheckpointManager "
                             "(construct the Trainer with ckpt_dir=)")
        start = int(jax.device_get(self.state["step"]))
        if start != policy.start_step:
            raise ValueError(
                f"state is at step {start}, policy starts at "
                f"{policy.start_step}")
        policy.start()
        self.ckpt.save(self.state, policy.start_step)  # always restorable
        while not policy.done():
            plan = policy.execute_step(
                schedule.events_at(policy.attempt))
            if any(d.kind == "reshard" for d in plan.decisions):
                # step times legitimately change with the mesh: the
                # watchdog must re-learn its baseline, not flag every
                # post-reshard step against the old capacity's median
                self.watchdog.reset_window()
            if plan.pre_save is not None:
                # preemption notice: save before losing the pod
                self.ckpt.save(self.state, plan.pre_save)
            if plan.kind == "step":
                self._run_one_step(plan.step)
                if plan.post_save is not None:
                    self.ckpt.save(self.state, plan.post_save)
            elif plan.kind == "stall":
                self.s_stalls.inc()     # collective hung on a silent pod
            else:                       # "recover"
                self.s_failures.inc()
                self.ckpt.wait()        # surface async-save errors first
                self.state = self.ckpt.restore(self.state,
                                               step=plan.restore_to)
        self.ckpt.wait()
        final = int(jax.device_get(self.state["step"]))
        return {"final_step": final, "attempts": policy.attempt,
                "decisions": list(policy.decisions),
                "history": self.history}
