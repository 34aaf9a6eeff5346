"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Without ``--full`` it trains the REDUCED (smoke) config; ``--full``
trains the published widths on ONE device, with no mesh (e.g.
whisper-small fits one 16 GB TPU v5e chip with f32 AdamW state).  The
sharded step on a mesh is ``build_sharded_train``; ``chip_smoke.py
--chips 4`` drives it for stablelm-1.6b on a (data=1, model=4) mesh.
The trainer is the SimObject loop from ``repro.train.trainer`` with
checkpointing, heartbeat, straggler watchdog, and deterministic data.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
from typing import Any, Callable, Optional, Sequence, Tuple

import jax

from repro.configs import REGISTRY, get_config, smoke
from repro.configs.base import ShapeConfig
from repro.data import SyntheticPipeline
from repro.dist.sharding import MeshSharder
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.models.api import Model
from repro.train import TrainOptions, build_train_step, init_train_state
from repro.train.step import default_options_for, train_state_specs
from repro.train.trainer import Trainer


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b",
                    choices=sorted(REGISTRY))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (one device)")
    ap.add_argument("--d-model", type=int, default=None,
                    help="override smoke width (e.g. ~100M model)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def build_trainer(args: argparse.Namespace) -> Trainer:
    """The trainer (random init from ``--seed``) that ``main`` runs."""
    cfg = get_config(args.arch)
    if not args.full:
        cfg = smoke(cfg)
        if args.d_model:
            hd = max(16, args.d_model // max(cfg.n_heads, 1))
            cfg = dataclasses.replace(
                cfg, d_model=args.d_model, d_ff=args.d_model * 3,
                d_head=hd, vocab_size=4096,
                n_layers=max(cfg.n_layers, 8))
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    model = build_model(cfg)
    opts = dataclasses.replace(default_options_for(cfg), peak_lr=args.lr,
                               warmup=10, total_steps=args.steps,
                               chunk=1024)
    state = jax.jit(functools.partial(init_train_state, model, opts=opts))(
        jax.random.PRNGKey(args.seed))
    tr = Trainer(model=model, train_step=build_train_step(model, opts),
                 pipeline=SyntheticPipeline(cfg, shape, seed=args.seed),
                 state=state, ckpt_dir=args.ckpt_dir, ckpt_interval=50)
    tr.instantiate()
    return tr


def build_sharded_train(model: Model, opts: TrainOptions,
                        sharder: MeshSharder, shape: ShapeConfig
                        ) -> Tuple[Callable, Callable, Any]:
    """The train step jitted over ``sharder``'s mesh and rules
    (``MeshSharder(mesh, make_rules(cfg, shape, mesh))``).

    Returns ``(step, init, batch_shardings)``: ``step(state, batch)``
    donates its state; ``init(key)`` builds the state directly in its
    shards (``out_shardings``), so no device ever holds all of it.
    """
    _, state_axes = train_state_specs(model, opts)
    state_sh = sharder.param_shardings(state_axes)
    batch_sh = sharder.batch_shardings(model.input_specs(shape), model.cfg)
    step = jax.jit(build_train_step(model, opts, sharder,
                                    param_axes=state_axes["params"]),
                   in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, None), donate_argnums=(0,))
    init = jax.jit(functools.partial(init_train_state, model, opts=opts),
                   out_shardings=state_sh)
    return step, init, batch_sh


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    use_compile_cache()
    tr = build_trainer(args)
    res = tr.run(args.steps)
    print(tr.stats.dump_text())
    h = res["history"]
    print(json.dumps({"first_loss": h[0]["loss"], "last_loss": h[-1]["loss"],
                      "steps": res["final_step"],
                      "median_step_s": tr.watchdog.median()}, indent=1))


if __name__ == "__main__":
    main()
