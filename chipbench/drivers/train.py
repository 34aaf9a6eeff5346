"""Training driver: the program's ``Trainer`` on one chip.

Set-up makes the weights on the device from the seed, builds the
trainer as ``repro.launch.train.build_trainer`` does (no checkpoint
directory) around the program's ``SyntheticPipeline`` seeded from
``--seed``, with uniform token ids (``learnable=False``) so that the
first steps' gradients are not shaped by its fixed Markov stream, and
runs its first three steps through ``Trainer.run``: the first compiles,
and the three are what the reference follows, on the same batches, read
again from the pipeline.  The first is taken at a learning rate of 0
(the warm-up starts there), so the parameters' change covers the other
two, and the first gradient is read from Adam's moment.  The window then calls
``Trainer.run`` in chunks of ``steps_per_call`` steps until ``--seconds``
have passed; each step's batch is built on the host, in series with the
step, as the trainer does.  With ``--trace 1`` the profiler runs from
``trace.start_s`` into the window for ``trace.length_s`` seconds,
between chunks.
"""

from __future__ import annotations

import math
import shutil
import tempfile
import time
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench import checks, harness, weights
from chipbench import trace as tr

CHECKED_STEPS = 3


def run(ctx: harness.Context) -> Dict:
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticPipeline
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.train import TrainOptions, build_train_step
    from repro.train.trainer import Trainer

    mix, sizes, o = ctx.traffic, ctx.cfg["sizes"], ctx.traffic["optimizer"]
    arch = ctx.arch()
    model = build_model(arch)
    opts = TrainOptions(peak_lr=o["peak_lr"], warmup=o["warmup"],
                        total_steps=o["total_steps"],
                        weight_decay=o["weight_decay"],
                        clip_norm=o["clip_norm"], chunk=int(mix["chunk"]))
    shapes = model.param_specs()[0]
    key = harness.jax_key(ctx.seed)
    params = weights.make(shapes, key, ctx.family.leaf_init)
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    del params
    batch, seq = int(mix["batch"]), int(mix["seq"])
    feed = SyntheticPipeline(arch, ShapeConfig(ctx.cell["name"], seq, batch,
                                               "train"),
                             seed=ctx.seed % 2 ** 64, learnable=False)
    trainer = Trainer(model=model, train_step=build_train_step(model, opts),
                      pipeline=feed, state=state)
    trainer.instantiate()
    del state

    # the first steps, through the window's own call and feed
    trainer.run(1)
    prog = {"grad": {k: v / (1 - o["b1"]) for k, v in checks.leaf_norms(
        trainer.state["opt"]["m"]).items()}}
    trainer.run(CHECKED_STEPS - 1)
    prog["change"] = checks.change_norms(
        trainer.state["params"],
        weights.make(shapes, key, ctx.family.leaf_init))
    prog["losses"] = [h["loss"] for h in trainer.history[:CHECKED_STEPS]]

    per_call = int(mix.get("steps_per_call", 1))
    trace_cfg = mix.get("trace") if ctx.trace else None
    trace_dir, marks, ann = None, {}, None
    mark = ctx.log.mark()
    first = len(trainer.history)
    t0 = time.perf_counter()
    setup_s = t0 - ctx.started
    t = t0
    while t - t0 < ctx.seconds:
        if trace_cfg and trace_dir is None and \
                t - t0 >= trace_cfg["start_s"]:
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(trace_dir)
            ann = jax.profiler.TraceAnnotation(tr.WINDOW)
            ann.__enter__()
            marks["start"] = (time.perf_counter(), len(trainer.history))
        elif ann is not None and "stop" not in marks and \
                t - marks["start"][0] >= trace_cfg["length_s"]:
            marks["stop"] = (time.perf_counter(), len(trainer.history))
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        with jax.profiler.TraceAnnotation("chipbench.trainer.run"):
            trainer.run(per_call)
        t = time.perf_counter()
    if ann is not None and "stop" not in marks:
        marks["stop"] = (time.perf_counter(), len(trainer.history))
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    in_window = ctx.log.since(mark)
    window = trainer.history[first:]
    seconds = t - t0
    peak = harness.memory_peak(ctx.devices)
    del trainer

    record: Dict = {"steps": len(window)}
    trace = None
    if trace_dir:
        trace = tr.Trace(tr.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        record["trace"] = trace
        record["traced"] = {"steps": marks["stop"][1] - marks["start"][1],
                            "seconds": marks["stop"][0] - marks["start"][0],
                            "batch": batch, "seq": seq}

    params0 = weights.make(shapes, key, ctx.family.leaf_init)
    batches = [feed.batch(i) for i in range(CHECKED_STEPS)]
    rows = int(mix["check"]["block_rows"])
    ref = checks.train_reference(ctx.family, sizes, o, params0, batches, rows)
    numbers, worst = checks.train_numbers(prog, ref)
    control = {}
    if ctx.control:
        for name, kw in (("fp8", {"precision": "fp8"}),
                         ("half_batch", {"rows": batch // 2})):
            control[name] = checks.train_numbers(checks.train_reference(
                ctx.family, sizes, o, params0, batches, rows, **kw),
                ref)[0]
    failed = sum(1 for h in window if not math.isfinite(h["loss"]))
    return {
        "e2e": {"train_tokens_per_s":
                len(window) * batch * seq / seconds,
                "setup_s": setup_s},
        "record": record, "trace": trace,
        "checks": dict({k: (v, float(mix["check"][k]))
                        for k, v in numbers.items()},
                       failed_steps=(failed, 0)),
        "attempted": len(window), "failed": failed,
        "memory_peak_bytes": peak,
        "control": control,
        "notes": dict(in_window, window_s=seconds, steps=len(window),
                      losses=prog["losses"], ref_losses=ref["losses"],
                      worst_leaf=worst),
    }
