"""Continuous-batching inference server (vLLM-style slot scheduler).

A fixed decode batch of B slots; requests from a queue are prefilled
one at a time (B=1 prefill) and their caches inserted into free slots;
every loop iteration advances ALL active slots by one token with a
single batched decode step (per-slot ``cur_len`` vector).  Finished
slots (max tokens or EOS) are freed.  The server is a SimObject with
throughput/latency stats — and the DES can model the same policy at pod
scale for the dse_sweep benchmark.

Each loop iteration is marked with ``jax.profiler`` spans, recorded only
while a profiler session is active (``jax.profiler.trace``), on the host
thread that runs the loop.  A profile then shows, for every stretch in
which the device sat idle, which part of the loop the host was in:

- ``serve.step``: one iteration, as the profiler's step
  (``step_num`` = the server's decode-step count before the step);
- ``serve.fill``: admitting queued requests into free slots, holding a
  ``serve.prefill`` span per request (``rid``, ``prompt_len``): the
  prefill, the cache insert and the read-back of the first token;
- ``serve.dispatch``: the step's inputs put on the device and the
  batched decode step called (it runs asynchronously);
- ``serve.sync``: waiting for the step's tokens to reach the host;
- ``serve.emit``: appending the tokens to the requests and retiring
  finished ones.

The stats ``sync_wait`` and ``loop_host`` split every iteration into the
seconds spent in ``serve.sync`` and the rest; ``queue_wait`` is each
request's time from ``serve`` being called to its slot insert.  They are
kept whether or not a profiler runs.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.simobject import Param, SimObject
from repro.models.api import Model
from repro.serve.policy import SlotScheduler
from repro.serve.step import build_decode_step, build_prefill_step

SPAN_STEP = "serve.step"
SPAN_FILL = "serve.fill"
SPAN_PREFILL = "serve.prefill"
SPAN_DISPATCH = "serve.dispatch"
SPAN_SYNC = "serve.sync"
SPAN_EMIT = "serve.emit"


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    eos_token: Optional[int] = None
    extras: Dict[str, Any] = field(default_factory=dict)
    # filled by the server:
    output: List[int] = field(default_factory=list)
    submit_time: float = 0.0
    insert_time: float = 0.0
    finish_time: float = 0.0


class BatchServer(SimObject):
    slots = Param(int, 4, "decode batch size")
    seq_capacity = Param(int, 128, "KV/state capacity per slot")

    def __init__(self, name: str = "server", *, model: Model, params,
                 **kw):
        super().__init__(name, **kw)
        self.model = model
        self.params = params
        self._prefill = jax.jit(build_prefill_step(
            model, seq_capacity=self.seq_capacity))
        self._decode = jax.jit(build_decode_step(model))
        self.s_tokens = self.stats.scalar("tokens_out", "tokens generated")
        self.s_requests = self.stats.scalar("requests", "requests served")
        self.s_latency = self.stats.distribution("latency", unit="s")
        self.s_decode_steps = self.stats.scalar("decode_steps")
        self.s_sync_wait = self.stats.distribution(
            "sync_wait", "waiting for a decode step's tokens", unit="s")
        self.s_loop_host = self.stats.distribution(
            "loop_host", "rest of a loop iteration", unit="s")
        self.s_queue_wait = self.stats.distribution(
            "queue_wait", "submit to slot insert, per request", unit="s")
        self.s_throughput = self.stats.formula(
            "tokens_per_decode_step",
            lambda: self.s_tokens.value() / max(self.s_decode_steps.value(),
                                                1))

    # ------------------------------------------------------------------
    def _prefill_into(self, cache, slot: int, req: Request):
        """Prefill ``req`` alone (B=1) and copy its cache into ``slot``.
        Returns (cache, last-position logits (Vp,))."""
        batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32),
                 **{k: jnp.asarray(v)[None] for k, v in req.extras.items()}}
        logits, rcache = self._prefill(self.params, batch)
        cache = jax.tree.map(
            lambda c, rc: jax.lax.dynamic_update_slice_in_dim(
                c, rc.astype(c.dtype), slot, 1),
            cache, rcache)
        return cache, logits[0, -1]

    def replay_logits(self, req: Request) -> np.ndarray:
        """Logits of a served request through this server's own steps.

        Replays ``req.prompt`` and ``req.output`` alone in slot 0 of a
        fresh cache.  Row t (f32, shape (Vp,)) is what the prefill
        (t = 0) or the t-th decode step computes for the token after
        ``prompt + output[:t]``, so a full forward pass over
        ``prompt + output[:-1]`` must give the same rows.
        """
        B = self.slots
        cache = self.model.init_cache(B, self.seq_capacity)
        cache, row = self._prefill_into(cache, 0, req)
        rows = [row]
        for i, t in enumerate(req.output[:-1]):
            # fresh host arrays each step: the CPU backend may alias a
            # numpy input until the (asynchronous) step has read it
            tok = np.zeros((B, 1), np.int32)
            tok[0, 0] = t
            cur_len = np.zeros((B,), np.int32)
            cur_len[0] = len(req.prompt) + i
            _, logits, cache = self._decode(self.params, {
                "tokens": jnp.asarray(tok),
                "cache": cache,
                "cur_len": jnp.asarray(cur_len),
            })
            rows.append(logits[0, -1])
        return np.asarray(jnp.stack(rows).astype(jnp.float32))

    def serve(self, requests: List[Request]) -> List[Request]:
        """Serve ``requests`` to completion.

        All scheduling (admission order, slot assignment, finish
        detection) is delegated to the pure :class:`SlotScheduler`
        policy — the same object the DES ``ServeSim`` drives at pod
        scale — and the decision log of the run is left on
        ``self.scheduler`` for inspection/equivalence testing.

        Requests must carry **unique rids** (they key the decision
        log) and prompts must fit ``seq_capacity``; the policy raises
        ``ValueError`` otherwise — previously duplicate rids were
        silently tolerated and oversized prompts overflowed the cache.
        """
        B = self.slots
        cap = self.seq_capacity
        cache = self.model.init_cache(B, cap)
        cur_len = np.zeros((B,), np.int32)
        last_tok = np.zeros((B, 1), np.int32)
        by_rid = {r.rid: r for r in requests}
        sched = SlotScheduler(B, cap)
        self.scheduler = sched
        for r in requests:
            r.submit_time = time.perf_counter()
            sched.submit(r.rid, len(r.prompt), r.max_new_tokens)
        done: List[Request] = []

        def insert(slot: int, req: Request) -> None:
            nonlocal cache
            with jax.profiler.TraceAnnotation(
                    SPAN_PREFILL, rid=req.rid, prompt_len=len(req.prompt)):
                req.insert_time = time.perf_counter()
                cache, logits = self._prefill_into(cache, slot, req)
                tok = int(jax.device_get(jnp.argmax(
                    logits.astype(jnp.float32))))
                req.output.append(tok)
                last_tok[slot, 0] = tok
                cur_len[slot] = len(req.prompt)
            self.s_queue_wait.sample(req.insert_time - req.submit_time)

        while not sched.idle():
            t_step = time.perf_counter()
            with jax.profiler.StepTraceAnnotation(
                    SPAN_STEP, step_num=int(self.s_decode_steps.value())):
                # fill free slots (prefill emits each request's first token)
                with jax.profiler.TraceAnnotation(SPAN_FILL):
                    for slot, rid in sched.fill():
                        insert(slot, by_rid[rid])
                # one batched decode step for all active slots
                with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
                    nxt, _, cache = self._decode(self.params, {
                        "tokens": jnp.asarray(last_tok),
                        "cache": cache,
                        "cur_len": jnp.asarray(cur_len),
                    })
                with jax.profiler.TraceAnnotation(SPAN_SYNC):
                    t_sync = time.perf_counter()
                    nxt = np.asarray(jax.device_get(nxt))
                    sync = time.perf_counter() - t_sync
                self.s_decode_steps.inc()
                sched.note_step()
                with jax.profiler.TraceAnnotation(SPAN_EMIT):
                    for slot in sched.active_slots():
                        req = by_rid[sched.active[slot]]
                        tok = int(nxt[slot, 0])
                        req.output.append(tok)
                        self.s_tokens.inc()
                        cur_len[slot] += 1
                        last_tok[slot, 0] = tok
                        if sched.complete_token(slot,
                                                is_eos=tok == req.eos_token):
                            req.finish_time = time.perf_counter()
                            self.s_requests.inc()
                            self.s_latency.sample(
                                req.finish_time - req.submit_time)
                            done.append(req)
            self.s_sync_wait.sample(sync)
            self.s_loop_host.sample(time.perf_counter() - t_step - sync)
        return done
