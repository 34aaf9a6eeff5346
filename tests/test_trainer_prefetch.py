"""The trainer builds step k + 1's batch on a worker thread while step k
runs.  Prefetching may change when a batch is built, never what a step
computes: losses and parameters are bit-identical to a plain loop, a
rewind rebuilds the step it lands on, and an exception in the worker is
raised by the step that needed the batch.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.train.ft_policy import FailureEvent, FailureSchedule, FTPolicy
from repro.train import trainer as T
from repro.train.trainer import SimulatedFailure, Trainer

STEPS = 12
SRC = str(Path(T.__file__).resolve().parents[2])    # holds ``repro``


class _Pipeline:
    """A pure function of the step, as ``SyntheticPipeline`` is; raises
    ``error`` for the steps in ``fail_for``."""

    def __init__(self, fail_for=(), error=ValueError):
        self.fail_for, self.error = set(fail_for), error

    def batch(self, step):
        if step in self.fail_for:
            raise self.error(f"no batch for step {step}")
        rng = np.random.default_rng((5, step))
        return {"x": rng.standard_normal((3, 4)).astype(np.float32),
                "w": np.full((4,), step % 5, np.int32)}


def _step(state, batch):
    g = jnp.mean(batch["x"], axis=0) + 0.01 * batch["w"]
    params = state["params"] * 0.9 - 0.1 * g
    return ({"params": params, "step": state["step"] + 1},
            {"loss": jnp.sum(params ** 2)})


def _state():
    return {"params": jnp.linspace(-1.0, 1.0, 4, dtype=jnp.float32),
            "step": jnp.asarray(0, jnp.int32)}


def _trainer(pipeline=None, **kw):
    tr = Trainer(model=None, train_step=_step,
                 pipeline=pipeline or _Pipeline(), state=_state(), **kw)
    tr.instantiate()
    return tr


@pytest.fixture(scope="module")
def plain():
    """Losses and final params of a loop with no trainer and no worker."""
    step, state, pipe = jax.jit(_step), _state(), _Pipeline()
    losses = []
    for k in range(STEPS):
        state, m = step(state, {n: jnp.asarray(v)
                                for n, v in pipe.batch(k).items()})
        losses.append(float(m["loss"]))
    return losses, np.asarray(state["params"])


@pytest.mark.parametrize("calls", [(STEPS,), (1,) * STEPS, (2, 5, 1, 4)],
                         ids=["one_run", "run_1_each", "mixed_runs"])
def test_prefetched_steps_match_a_plain_loop(plain, calls):
    tr = _trainer()
    for n in calls:
        tr.run(n)
    losses, params = plain
    assert [h["loss"] for h in tr.history] == losses
    np.testing.assert_array_equal(np.asarray(tr.state["params"]), params)
    assert [h["step"] for h in tr.history] == list(range(STEPS))


def test_run_1_in_a_loop_takes_every_batch_but_the_first_from_the_worker():
    tr = _trainer()
    for _ in range(STEPS):
        tr.run(1)
    assert tr.s_prefetched.value() == STEPS - 1
    assert [h["prefetched"] for h in tr.history] == \
        [False] + [True] * (STEPS - 1)
    assert tr.s_input_time.count == STEPS
    assert min(h["input_s"] for h in tr.history) >= 0.0
    assert tr.stats.flat()["trainer.input_prefetched"] == STEPS - 1


def test_rewind_through_run_rebuilds_the_step_it_lands_on(plain, tmp_path):
    tr = _trainer(ckpt_dir=str(tmp_path), ckpt_interval=4)
    res = tr.run(STEPS, fail_at={6: SimulatedFailure("node died")})
    steps = [h["step"] for h in res["history"]]
    # steps 0-5 ran, 6 failed, the restore went back to the save at 4
    assert steps == list(range(6)) + list(range(4, STEPS))
    assert [h["loss"] for h in res["history"]] == \
        [plain[0][k] for k in steps]
    np.testing.assert_array_equal(np.asarray(tr.state["params"]), plain[1])
    flags = [h["prefetched"] for h in res["history"]]
    assert flags[0] is False and flags[6] is False     # first; re-run 4
    assert all(flags[1:6])
    assert tr.s_prefetched.value() == sum(flags)


def test_rewind_through_run_ft_rebuilds_the_step_it_lands_on(plain,
                                                            tmp_path):
    pods = 4
    sched = FailureSchedule(
        (FailureEvent(9, "pod_failed", pod=1, repair=0),), pods=pods)
    pol = FTPolicy(get_config("deepseek-67b"), num_steps=STEPS,
                   ckpt_interval=4, pods=pods, chips_per_pod=16)
    tr = _trainer(ckpt_dir=str(tmp_path))
    res = tr.run_ft(sched, pol)
    assert res["final_step"] == STEPS
    hist = res["history"]
    steps = [h["step"] for h in hist]
    assert len(steps) > STEPS                  # some steps ran again
    assert [h["loss"] for h in hist] == [plain[0][k] for k in steps]
    np.testing.assert_array_equal(np.asarray(tr.state["params"]), plain[1])
    rewinds = [i for i in range(1, len(steps))
               if steps[i] != steps[i - 1] + 1]
    assert rewinds and hist[0]["prefetched"] is False
    assert all(hist[i]["prefetched"] is False for i in rewinds)


@pytest.mark.parametrize("calls", [(1, 1, 1), (3,)],
                         ids=["run_1_each", "one_run"])
def test_worker_error_surfaces_from_the_step_that_needs_the_batch(calls):
    class Dropped(RuntimeError):
        pass

    tr = _trainer(_Pipeline(fail_for={2}, error=Dropped))
    *before, last = calls
    for n in before:
        tr.run(n)                  # builds step 2's batch ahead, in vain
    assert [h["step"] for h in tr.history] == list(range(sum(before)))
    with pytest.raises(Dropped, match="step 2"):
        tr.run(last)
    assert [h["step"] for h in tr.history] == [0, 1]


def test_a_batch_no_step_takes_is_dropped_without_error():
    tr = _trainer(_Pipeline(fail_for={STEPS}))
    res = tr.run(STEPS)                 # the worker fails on step STEPS
    assert res["final_step"] == STEPS
    assert tr.s_prefetched.value() == STEPS - 1


def test_exit_waits_for_at_most_the_batch_in_flight():
    """A process whose trainer is still building a batch ahead exits
    once that batch is built."""
    code = textwrap.dedent("""
        import time
        import jax.numpy as jnp
        import numpy as np
        from repro.train.trainer import Trainer

        class Slow:
            def batch(self, step):
                if step:
                    time.sleep(1.0)
                return {"x": np.ones((2,), np.float32)}

        def step(state, batch):
            return ({"p": state["p"] + batch["x"], "step": state["step"] + 1},
                    {"loss": jnp.sum(state["p"])})

        tr = Trainer(model=None, train_step=step, pipeline=Slow(),
                     state={"p": jnp.zeros((2,)), "step": jnp.asarray(0)})
        tr.instantiate()
        tr.run(1)
        print("ran", tr.history[0]["prefetched"], flush=True)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ran False"
