"""Chip smoke test: the serving and training paths at published widths.

    python chip_smoke.py            # one TPU chip
    python chip_smoke.py --chips 4  # four TPU chips: the sharded step only

One chip, in order, with random weights drawn from ``--seed``:

1. serve: stablelm-1.6b (unreduced) through ``BatchServer``, built by
   ``repro.launch.serve.build`` (what ``python -m repro.launch.serve
   --full`` runs): 4 slots x 1024 capacity, 8 requests with prompts of
   128 or 512 tokens, 32 new tokens each.  Passes when every request
   returns 32 tokens, every request's logits, replayed through the
   server's own prefill and decode steps, are finite, and one request's
   cache-path logits match a full forward pass (``Model.train_logits``)
   over the same tokens within ``SERVE_RTOL``.  The requests are then
   served again, warm, to time the loop; the outputs must repeat.
2. train: 3 steps of whisper-small (unreduced; batch 4, decoder length
   448, encoder 1500 frames) through ``Trainer.run``, built by
   ``repro.launch.train.build_trainer`` (what ``python -m
   repro.launch.train --full`` runs).  Passes when every loss is finite.

Four chips (``--chips 4``): 3 steps of the stablelm-1.6b train step
(batch 4, sequence 1024) sharded on a (data=1, model=4) mesh by
``repro.launch.train.build_sharded_train``, its state built in its
shards.  Passes when every loss is finite and the first step's loss
matches the unsharded ``Model.train_logits`` loss on one device, for the
same params and batch, within ``LOSS_RTOL``.

Each phase prints one ``phase {json}`` line: wall time, compile count and
time, tokens or step times, and device memory.  The last line of stdout
is ``{"ok": true, "device": {...}}``.  With no TPU the script exits
non-zero before any phase; a failed check or phase raises, so it never
prints that line.  It starts no subprocess.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.data import SyntheticPipeline  # noqa: E402
from repro.dist.sharding import MeshSharder, make_rules  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.layers import cross_entropy  # noqa: E402
from repro.train.step import default_options_for  # noqa: E402

# Tolerances for bf16 compute.  bf16 keeps 8 significant bits (relative
# spacing 2**-8, about 0.4%).  The cache path and the full forward pass
# round differently in each of the 24 layers, and those differences add
# up like a random walk: about sqrt(24) * 0.4% = 2% of the logits'
# scale (2.2% measured on the CPU at 24 layers, d_model 512).  A wrong
# cache slot or position gives errors of the logits' own size (43%
# measured for such a fault), so 10% separates the two.
SERVE_RTOL = 0.1
# The sharded and the one-device loss differ only in reduction order
# and layout; the loss is a mean over 4096 tokens, so its rounding
# differences average out: 1% of it is several bf16 spacings.
LOSS_RTOL = 0.01


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class CompileLog:
    """Counts backend compilations and their seconds, from JAX's own
    monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self):
        return self.count, self.seconds

    def since(self, mark):
        return {"compiles": self.count - mark[0],
                "compile_s": self.seconds - mark[1]}


def memory(devices):
    """bytes_in_use and peak_bytes_in_use (process lifetime) per device."""
    out = []
    for d in devices:
        st = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": st.get("bytes_in_use"),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use")})
    return out


def report(phase: str, **fields) -> None:
    print("phase " + json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_serve(log: CompileLog, seed: int) -> None:
    args = serve.parse_args([
        "--full", "--arch", "stablelm-1.6b", "--slots", "4",
        "--seq-capacity", "1024", "--requests", "8", "--max-new", "32",
        "--seed", str(seed)])
    t0, m0 = time.perf_counter(), log.mark()
    srv, reqs = serve.build(args)
    done = srv.serve(reqs)
    cold_s = time.perf_counter() - t0
    cold = log.since(m0)
    require(len(done) == args.requests,
            f"{len(done)} of {args.requests} requests finished")
    for r in done:
        require(len(r.output) == args.max_new,
                f"request {r.rid} returned {len(r.output)} tokens")

    t0, m0 = time.perf_counter(), log.mark()
    finite = {r.rid: bool(np.isfinite(srv.replay_logits(r)).all())
              for r in done}
    require(all(finite.values()), f"non-finite logits: {finite}")
    first = min(done, key=lambda r: r.rid)
    cmp = serve.check_against_forward(srv, first)
    check_s = time.perf_counter() - t0
    check = log.since(m0)
    require(cmp["rel_err"] <= SERVE_RTOL,
            f"cache path vs forward: {cmp} (limit {SERVE_RTOL})")

    # warm: the same requests again, every program already compiled
    warm_reqs = [dataclasses.replace(r, output=[]) for r in reqs]
    t0, m0 = time.perf_counter(), log.mark()
    steps0 = srv.s_decode_steps.value()
    warm = srv.serve(warm_reqs)
    warm_s = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in warm)
    require({r.rid: r.output for r in warm}
            == {r.rid: r.output for r in done},
            "warm run produced other tokens than the cold run")
    report("serve", arch="stablelm-1.6b", slots=args.slots,
           seq_capacity=args.seq_capacity, requests=args.requests,
           prompt_lens=sorted({len(r.prompt) for r in reqs}),
           max_new=args.max_new,
           cold_wall_s=cold_s, cold=cold, check_wall_s=check_s,
           check=check, warm_wall_s=warm_s, warm=log.since(m0),
           warm_tokens=tokens, warm_tokens_per_s=tokens / warm_s,
           warm_decode_steps=srv.s_decode_steps.value() - steps0,
           check_request=first.rid,
           check_prompt_len=len(first.prompt), cache_vs_forward=cmp,
           rtol=SERVE_RTOL, memory=memory(jax.devices()[:1]))


def phase_train(log: CompileLog, seed: int) -> None:
    args = train.parse_args([
        "--full", "--arch", "whisper-small", "--batch", "4",
        "--seq", "448", "--steps", "3", "--seed", str(seed)])
    t0, m0 = time.perf_counter(), log.mark()
    tr = train.build_trainer(args)
    res = tr.run(args.steps)
    wall = time.perf_counter() - t0
    losses = [h["loss"] for h in res["history"]]
    require(len(losses) == args.steps, f"{len(losses)} steps ran")
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    report("train", arch="whisper-small", batch=args.batch,
           dec_seq=args.seq, enc_seq=tr.model.cfg.enc_seq,
           wall_s=wall, **log.since(m0), losses=losses,
           step_s=[h["time_s"] for h in res["history"]],
           memory=memory(jax.devices()[:1]))


def phase_sharded(log: CompileLog, seed: int) -> None:
    cfg = get_config("stablelm-1.6b")
    model = build_model(cfg)
    shape = ShapeConfig("chip_smoke", 1024, 4, "train")
    opts = dataclasses.replace(default_options_for(cfg), warmup=1,
                               total_steps=3, chunk=1024)
    mesh = make_mesh((1, 4), ("data", "model"))
    devices = list(mesh.devices.flat)
    sharder = MeshSharder(mesh, make_rules(cfg, shape, mesh))
    step, init, batch_sh = train.build_sharded_train(model, opts, sharder,
                                                     shape)
    pipe = SyntheticPipeline(cfg, shape, seed=seed)

    t0, m0 = time.perf_counter(), log.mark()
    state = init(jax.random.PRNGKey(seed))
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t0
    init_mem = memory(devices)
    host_params = jax.device_get(state["params"])   # for the reference
    losses, step_s = [], []
    for i in range(3):
        t1 = time.perf_counter()
        state, metrics = step(state, jax.device_put(pipe.batch(i), batch_sh))
        losses.append(float(metrics["loss"]))
        step_s.append(time.perf_counter() - t1)
    sharded = log.since(m0)
    step_mem = memory(devices)
    del state, metrics
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")

    # the reference: the same params and batch, unsharded, on one device
    def ref_loss(params, batch):
        logits, _ = model.train_logits(params, batch, chunk=opts.chunk)
        return cross_entropy(logits, batch["labels"], cfg,
                             mask=batch["mask"])

    t0, m0 = time.perf_counter(), log.mark()
    params = jax.device_put(host_params, devices[0])
    del host_params
    batch = jax.device_put(pipe.batch(0), devices[0])
    ref = float(jax.jit(ref_loss)(params, batch))
    ref_s = time.perf_counter() - t0
    rel = abs(losses[0] - ref) / abs(ref)
    require(rel <= LOSS_RTOL,
            f"sharded loss {losses[0]} vs one-device {ref} (limit "
            f"{LOSS_RTOL})")
    report("sharded_train", arch="stablelm-1.6b", mesh={"data": 1,
                                                         "model": 4},
           batch=shape.global_batch, seq=shape.seq_len, init_s=init_s,
           **sharded, losses=losses, step_s=step_s,
           memory_after_init=init_mem, memory_after_steps=step_mem)
    report("reference", device=devices[0].id, loss=ref,
           sharded_first_loss=losses[0], rel_diff=rel, rtol=LOSS_RTOL,
           wall_s=ref_s, **log.since(m0))


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
                 "it never falls back to the CPU")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} device(s)")
    log = CompileLog()
    report("setup", compile_cache=use_compile_cache(),
           kind=devices[0].device_kind, count=len(devices),
           jax=jax.__version__)
    if args.chips == 4:
        phase_sharded(log, args.seed)
    else:
        phase_serve(log, args.seed)
        phase_train(log, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
