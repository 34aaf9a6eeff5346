"""The entry points' build functions, at smoke width on the CPU: the serve and
train launchers, the sharded train step, the compile-cache placement,
and chip_smoke.py's refusal to run without a TPU."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.configs.base import ShapeConfig
from repro.data import SyntheticPipeline
from repro.dist.sharding import MeshSharder, make_rules
from repro.launch import compile_cache, serve, train
from repro.launch.mesh import single_device_mesh
from repro.models import build_model
from repro.models.layers import cross_entropy
from repro.train.step import TrainOptions

REPO = Path(__file__).resolve().parents[1]

# the build functions jit-compile real (reduced) models
pytestmark = pytest.mark.slow


def _served(arch: str):
    args = serve.parse_args([
        "--arch", arch, "--requests", "3", "--slots", "2", "--max-new",
        "5", "--seq-capacity", "32"])
    srv, reqs = serve.build(args)
    return srv, srv.serve(reqs)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "whisper-small",
                                  "rwkv6-7b", "granite-4.0-h-micro"])
def test_cache_path_matches_forward(arch):
    """Prefill + decode through the cache give the full forward pass's
    logits, within bf16 noise (measured 0.8% at 4 layers)."""
    srv, done = _served(arch)
    assert sorted(len(r.output) for r in done) == [5, 5, 5]
    assert sorted({len(r.prompt) for r in done}) == [4, 16]
    for r in done:
        cmp = serve.check_against_forward(srv, r)
        assert cmp["rel_err"] <= 0.05, (r.rid, cmp)


def test_replay_reproduces_served_tokens():
    """The replay runs the server's own steps: its greedy tokens are the
    served ones, whatever slot the request had."""
    srv, done = _served("stablelm-1.6b")
    for r in done:
        rows = srv.replay_logits(r)
        assert rows.shape == (len(r.output), rows.shape[1])
        assert np.isfinite(rows).all()
        assert rows.argmax(-1).tolist() == r.output


def test_build_trainer_takes_finite_steps():
    args = train.parse_args(["--steps", "2", "--batch", "2", "--seq", "16"])
    tr = train.build_trainer(args)
    res = tr.run(args.steps)
    losses = [h["loss"] for h in res["history"]]
    assert res["final_step"] == 2
    assert np.isfinite(losses).all()


def test_sharded_step_matches_unsharded_loss():
    """build_sharded_train on a one-device mesh: the first step's loss is
    the unsharded forward pass's loss on the same params and batch."""
    cfg = smoke(get_config("stablelm-1.6b"))
    model = build_model(cfg)
    shape = ShapeConfig("t", 16, 2, "train")
    opts = TrainOptions(warmup=1, total_steps=2, chunk=16)
    mesh = single_device_mesh()
    step, init, batch_sh = train.build_sharded_train(
        model, opts, MeshSharder(mesh, make_rules(cfg, shape, mesh)), shape)
    state = init(jax.random.PRNGKey(0))
    params = jax.device_get(state["params"])
    batch = SyntheticPipeline(cfg, shape, seed=1).batch(0)
    state, metrics = step(state, jax.device_put(batch, batch_sh))
    logits, _ = jax.jit(model.train_logits)(params, batch)
    ref = cross_entropy(logits, batch["labels"], cfg, mask=batch["mask"])
    np.testing.assert_allclose(float(metrics["loss"]), float(ref),
                               rtol=1e-5)
    assert int(state["step"]) == 1


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_follows_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr
