"""The plain float32 references against the program at smoke width.

The program computes in bfloat16 from float32 weights, the reference in
float32, so they differ by bfloat16 rounding: about 0.4% per operation,
adding up over layers to about 1% of the logits' scale at two layers.
A wrong cache slot, position or weight moves them by the logits' own
scale."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import checks, harness, weights
from chipbench.refs import decoder_lm, encdec
from chipbench.tests import smoke


def arch(cfg):
    from repro.configs import get_config
    base = get_config(cfg["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    return dataclasses.replace(base, **{k: v for k, v in cfg["sizes"].items()
                                        if k in fields})


def test_decoder_lm_matches_prefill_and_decode():
    from repro.models import build_model
    from repro.serve import BatchServer, Request
    s = smoke.SMOKE_LM["sizes"]
    model = build_model(arch(smoke.SMOKE_LM))
    params = weights.make(model.param_specs()[0], harness.jax_key(3),
                          decoder_lm.leaf_init)
    srv = BatchServer(model=model, params=params, slots=3, seq_capacity=48)
    srv.instantiate()
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n, dtype=np.int32),
                    max_new_tokens=10) for i, n in enumerate((7, 16, 12, 9))]
    done = srv.serve(reqs)
    assert len(done) == 4
    for r in reqs[1:]:           # served in slots other than 0, or refilled
        toks = np.concatenate([r.prompt, np.asarray(r.output[:-1])])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(lambda p, t: decoder_lm.logits(
                p, t, s))(params, toks))[len(r.prompt) - 1:]
        got = srv.replay_logits(r)[:, :s["vocab_size"]]
        scale = np.max(np.abs(ref))
        assert np.max(np.abs(got - ref)) < 0.03 * scale
        gaps = ref.max(-1) - ref[np.arange(len(r.output)), r.output]
        assert gaps.max() < 0.03 * scale


def test_encdec_matches_train_step():
    """One program train step against the reference's: the loss, and
    every leaf's first-gradient norm from Adam's first moment."""
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.train import TrainOptions, build_train_step
    cfg, mix = smoke.SMOKE_ENCDEC, smoke.TRAIN
    s, o = cfg["sizes"], mix["optimizer"]
    model = build_model(arch(cfg))
    shapes = model.param_specs()[0]
    params = weights.make(shapes, harness.jax_key(5), encdec.leaf_init)
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticPipeline
    feed = SyntheticPipeline(arch(cfg), ShapeConfig(
        "smoke", mix["seq"], mix["batch"], "train"), seed=5, learnable=False)
    step = jax.jit(build_train_step(model, TrainOptions(
        peak_lr=o["peak_lr"], warmup=o["warmup"],
        total_steps=o["total_steps"], weight_decay=o["weight_decay"],
        clip_norm=o["clip_norm"])))
    state, metrics = step({"params": params, "opt": adamw_init(params),
                           "step": jnp.zeros((), jnp.int32)}, feed.batch(0))
    ref = checks.train_reference(encdec, s, o, params, [feed.batch(0)], 2)
    assert float(metrics["loss"]) == pytest.approx(ref["losses"][0],
                                                   rel=2e-3)
    grad = {k: v / (1 - o["b1"])
            for k, v in checks.leaf_norms(state["opt"]["m"]).items()}
    assert checks.rel_leaf_gap(grad, ref["grad"])[0] < 0.05
