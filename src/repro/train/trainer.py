"""Trainer: the driver loop as a SimObject (gem5-style composition).

The trainer is configured like every other g5x component — Params +
children (checkpoint manager, watchdog, heartbeat) — and exports a
stats group (loss, step-time distribution, straggler count, checkpoint
count) into the system tree.  Fault injection for tests: pass
``fail_at={step: exception}`` and the trainer demonstrates
checkpoint-restore recovery.

Each step is marked with ``jax.profiler`` spans, recorded only while a
profiler session is active (``jax.profiler.trace``), on the host thread
that runs the step:

- ``train.step``: the whole step, as the profiler's step
  (``step_num`` = the step);
- ``train.input``: the pipeline building the step's batch on the host
  and the batch put on the device;
- ``train.dispatch``: the call of the jitted step (it runs
  asynchronously);
- ``train.sync``: waiting for the step's loss to reach the host.

The stat ``input_time`` (and each history row's ``input_s``) holds the
seconds spent in ``train.input``, whether or not a profiler runs.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

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
        # stats
        self.s_loss = self.stats.scalar("loss", "last loss")
        self.s_steps = self.stats.scalar("steps", "steps completed")
        self.s_failures = self.stats.scalar("failures", "failures recovered")
        self.s_stragglers = self.stats.scalar("stragglers", "slow steps")
        self.s_stalls = self.stats.scalar("stalls",
                                          "attempts hung on a silent pod")
        self.s_step_time = self.stats.distribution("step_time", unit="s")
        self.s_input_time = self.stats.distribution(
            "input_time", "building and placing a step's batch", unit="s")
        self.history: list = []

    # ------------------------------------------------------------------
    def _run_one_step(self, step: int) -> None:
        """One real training step with all its bookkeeping (stats,
        watchdog, history, heartbeat) — the single copy both ``run``
        and ``run_ft`` execute."""
        with jax.profiler.StepTraceAnnotation(SPAN_STEP, step_num=step):
            with jax.profiler.TraceAnnotation(SPAN_INPUT):
                t_in = time.perf_counter()
                batch = {k: jax.numpy.asarray(v)
                         for k, v in self.pipeline.batch(step).items()}
                input_s = time.perf_counter() - t_in
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
                self.state, metrics = self._jitted(self.state, batch)
            with jax.profiler.TraceAnnotation(SPAN_SYNC):
                loss = float(jax.device_get(metrics["loss"]))
            dt = time.perf_counter() - t0
            if self.watchdog.record(step, dt):
                self.s_stragglers.inc()
            self.s_step_time.sample(dt)
            self.s_input_time.sample(input_s)
            self.s_loss.set(loss)
            self.s_steps.inc()
            self.history.append({"step": step, "loss": loss, "time_s": dt,
                                 "input_s": input_s})
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
                if self.ckpt and self.ckpt.latest_step() is not None:
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
