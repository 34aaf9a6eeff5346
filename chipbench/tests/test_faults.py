"""Whole runs with the timed path broken underneath: each fault that a
cell can have must make ``correct`` false.  The runs skip the look for a
chip and go on at smoke width on the CPU.

The cells run on one chip, so the fault "the exchange between chips left
out" has no path here; a four-chip cell brings its own case.  The control
(the reference at float8 in the program's place) is in
``test_control.py``."""

import jax
import jax.numpy as jnp
import pytest

from chipbench.tests import smoke


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = smoke.make_root(str(tmp_path_factory.mktemp("bench")))
    smoke.add_cell(r, "lm.serve", "lm", smoke.SMOKE_LM, "serve", smoke.SERVE,
                   like="stablelm-1.6b.serve-decode")
    smoke.add_cell(r, "ed.train", "ed", smoke.SMOKE_ENCDEC, "train",
                   smoke.TRAIN, like="whisper-small.train-b32")
    return r


def test_a_token_altered_where_it_is_produced(root, monkeypatch):
    import repro.serve.server as server
    real = server.build_decode_step

    def altered(model, *a, **k):
        step = real(model, *a, **k)

        def decode_step(params, batch):
            nxt, logits, cache = step(params, batch)
            return (nxt + 1) % model.cfg.vocab_size, logits, cache
        return decode_step

    monkeypatch.setattr(server, "build_decode_step", altered)
    line, _ = smoke.run(root, "lm.serve", seed=11)
    assert not line["correct"]
    assert line["checks"]["max_logit_gap"]["value"] > \
        line["checks"]["max_logit_gap"]["limit"]


def broken_step(monkeypatch, wrap):
    import repro.train as train
    real = train.build_train_step

    def build(model, opts=None, *a, **k):
        return wrap(real(model, opts, *a, **k))

    monkeypatch.setattr(train, "build_train_step", build)


def test_a_step_that_returns_its_state_unchanged(root, monkeypatch):
    def wrap(step):
        def train_step(state, batch):
            _, metrics = step(state, batch)
            return jax.tree.map(jnp.copy, state), metrics
        return train_step

    broken_step(monkeypatch, wrap)
    line, _ = smoke.run(root, "ed.train", seed=12)
    assert not line["correct"]
    assert line["checks"]["update_norm_rel"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(root, monkeypatch):
    def wrap(step):
        def train_step(state, batch):
            half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
            return step(state, half)
        return train_step

    broken_step(monkeypatch, wrap)
    line, _ = smoke.run(root, "ed.train", seed=13)
    assert not line["correct"]
