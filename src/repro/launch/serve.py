"""Serving launcher: the continuous-batching server on a registered model.

``python -m repro.launch.serve --arch whisper-small --requests 8`` serves
the reduced (smoke) config; ``--full`` serves the published widths
(e.g. ``--full --seq-capacity 1024``).  Prompts are an eighth and a half
of the slot capacity long, in turn: two lengths, because each new
prompt length compiles a whole prefill program.
"""

from __future__ import annotations

import argparse
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import REGISTRY, get_config, smoke
from repro.configs.base import ArchConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serve import BatchServer, Request


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b",
                    choices=sorted(REGISTRY))
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--seq-capacity", type=int, default=64,
                    help="KV/state capacity per slot")
    ap.add_argument("--full", action="store_true",
                    help="use the full published config (TPU-scale)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def make_requests(cfg: ArchConfig, n: int, prompt_lens: Sequence[int],
                  max_new: int, seed: int) -> List[Request]:
    """``n`` requests with prompt tokens (and stub frontend embeddings)
    drawn from ``seed``; prompt lengths cycle through ``prompt_lens``."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(prompt_lens[i % len(prompt_lens)])
        extras = {}
        if cfg.family == "vlm":
            extras["vision_embeds"] = rng.standard_normal(
                (cfg.n_vis, cfg.d_model)).astype(np.float32) * 0.1
        if cfg.family == "audio":
            extras["enc_embeds"] = rng.standard_normal(
                (cfg.enc_seq, cfg.d_model)).astype(np.float32) * 0.1
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, plen),
            max_new_tokens=max_new, extras=extras))
    return reqs


def build(args: argparse.Namespace) -> Tuple[BatchServer, List[Request]]:
    """The server (random weights from ``--seed``) and its requests."""
    cfg = get_config(args.arch)
    if not args.full:
        cfg = smoke(cfg)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    srv = BatchServer(model=model, params=params, slots=args.slots,
                      seq_capacity=args.seq_capacity)
    srv.instantiate()
    lens = (args.seq_capacity // 8, args.seq_capacity // 2)
    return srv, make_requests(cfg, args.requests, lens, args.max_new,
                              args.seed)


def check_against_forward(srv: BatchServer, req: Request) -> Dict[str, float]:
    """Compare a served request's cache-path logits with a full forward
    pass (``Model.train_logits``) over the same tokens.

    Returns the largest absolute logit difference, the reference's
    largest absolute logit, their ratio (``rel_err``), the RMS
    difference over the reference's RMS, and the share of positions
    whose argmax agrees.  Padded vocabulary columns are left out.
    """
    model = srv.model
    tokens = np.concatenate([req.prompt, np.asarray(req.output[:-1])])
    batch = {"tokens": tokens[None].astype(np.int32),
             **{k: np.asarray(v)[None] for k, v in req.extras.items()}}
    # srv.params: the server's compute-dtype tree, cast once from the caller's
    logits, _ = jax.jit(model.train_logits)(srv.params, batch)
    n, vocab = len(req.output), model.cfg.vocab_size
    ref = np.asarray(logits[0, -n:, :vocab].astype(np.float32))
    got = srv.replay_logits(req)[:, :vocab]
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    rms = float(np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)))
    return {"max_abs_err": err, "ref_max_abs": scale,
            "rel_err": err / scale, "rel_rms_err": rms,
            "argmax_agree": float(np.mean(
                got.argmax(-1) == ref.argmax(-1)))}


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    use_compile_cache()
    srv, reqs = build(args)
    done = srv.serve(reqs)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt_len={len(r.prompt)} -> {r.output}")
    flat = srv.stats.flat()
    print(" ".join(f"{k}={flat['server.' + k]}" for k in (
        "prefill_tokens", "cache_kv_bytes_per_slot",
        "cache_state_bytes_per_slot")))
    print(srv.stats.dump_text())


if __name__ == "__main__":
    main()
