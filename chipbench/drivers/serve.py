"""Serving driver: ``BatchServer.serve`` fed with bursts, back to back.

Set-up makes the weights on the device from the seed (float32, as the
server holds them), builds the server, and warms up exactly the mix's
shapes: one prefill per prompt length, the slot insert into several
slots, and the decode step.  The window then feeds ``serve`` one burst
after another.  Each request's ``output`` is a list of the harness's own
that stamps every token as the program appends it; the first append past
``--seconds`` raises, which ends the window.  With ``--trace 1`` the
profiler runs for ``trace.length_s`` seconds from ``trace.start_s`` into
the window, started and stopped from the same stamps.
"""

from __future__ import annotations

import shutil
import tempfile
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import checks, harness, stamps, weights
from chipbench import trace as tr
from chipbench import traffic as T


class WindowClosed(Exception):
    """Raised by the first token stamped past the deadline."""


class Clock:
    """Ends the window, and starts and stops the profiler, from the
    token stamps."""

    def __init__(self, srv, trace: Optional[Dict]):
        self.srv = srv
        self.trace = trace
        self.armed = False
        self.state = "off"          # off -> on -> done
        self.dir: Optional[str] = None
        self.marks: Dict[str, Dict] = {}

    def arm(self, t0: float, seconds: float) -> None:
        self.t0, self.deadline, self.armed = t0, t0 + seconds, True

    def counters(self, t: float) -> Dict:
        return {"t": t, "tokens_out": self.srv.s_tokens.value(),
                "decode_steps": self.srv.s_decode_steps.value()}

    def tick(self, t: float) -> None:
        if not self.armed:
            return
        if self.trace and self.state == "off" and \
                t >= self.t0 + self.trace["start_s"]:
            self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.dir)
            self._window = jax.profiler.TraceAnnotation(tr.WINDOW)
            self._window.__enter__()
            self.marks["start"] = self.counters(time.perf_counter())
            self.state = "on"
        elif self.state == "on" and \
                t >= self.marks["start"]["t"] + self.trace["length_s"]:
            self.stop()
        if t > self.deadline:
            self.armed = False
            raise WindowClosed

    def stop(self) -> None:
        if self.state != "on":
            return
        self.marks["stop"] = self.counters(time.perf_counter())
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


class Stamped(list):
    """A request's output list that stamps each token it is given."""

    def __init__(self, served: stamps.Served, clock: Clock):
        super().__init__()
        self.served = served
        self.clock = clock

    def append(self, tok) -> None:
        t = time.perf_counter()
        super().append(tok)
        self.served.times.append(t)
        self.clock.tick(t)


def traced_work(served: List[stamps.Served], lo: float, hi: float) -> Dict:
    """Prompt and output tokens stamped in ``(lo, hi]``, with the
    (query, key) pairs their attention reads."""
    out = {"prefills": 0, "prompt_tokens": 0, "prefill_attended": 0.0,
           "decode_tokens": 0, "decode_attended": 0.0}
    for r in served:
        for j, t in enumerate(r.times):
            if not lo < t <= hi:
                continue
            if j == 0:
                p = r.prompt_len
                out["prefills"] += 1
                out["prompt_tokens"] += p
                out["prefill_attended"] += p * (p + 1) / 2
            else:
                out["decode_tokens"] += 1
                out["decode_attended"] += r.prompt_len + j
    return out


def warmup_requests(mix: Dict, seed: int, vocab: int):
    """One request per prompt length, then more up to one past the
    slots, so that inserts land in several slots and a slot is refilled."""
    from repro.serve import Request
    lens = T.lengths(mix["prompt"])
    n = max(len(lens), int(mix["slots"]) + 1)
    rng = harness.np_rng(seed, 3)
    return [Request(rid=i, prompt=rng.integers(0, vocab, lens[i % len(lens)],
                                               dtype=np.int32),
                    max_new_tokens=int(mix.get("warmup_new_tokens", 2)))
            for i in range(n)]


def run(ctx: harness.Context) -> Dict:
    from repro.models import build_model
    from repro.serve import BatchServer, Request

    mix, sizes = ctx.traffic, ctx.cfg["sizes"]
    model = build_model(ctx.arch())
    params = weights.make(model.param_specs()[0], harness.jax_key(ctx.seed),
                          ctx.family.leaf_init)
    srv = BatchServer(model=model, params=params, slots=int(mix["slots"]),
                      seq_capacity=int(mix["seq_capacity"]))
    srv.instantiate()
    srv.serve(warmup_requests(mix, ctx.seed, sizes["vocab_size"]))

    clock = Clock(srv, mix.get("trace") if ctx.trace else None)
    served: List[stamps.Served] = []
    reqs: List = []
    mark = ctx.log.mark()
    before = clock.counters(0.0)
    t0 = time.perf_counter()
    setup_s = t0 - ctx.started
    clock.arm(t0, ctx.seconds)
    burst = 0
    try:
        while True:
            shape = T.burst_sizes(mix, ctx.seed, burst)
            prompts = T.prompt_tokens(ctx.seed, burst, shape,
                                      sizes["vocab_size"])
            batch = []
            for (plen, new), prompt in zip(shape, prompts):
                s = stamps.Served(plen, new)
                served.append(s)
                batch.append(Request(rid=len(reqs) + len(batch),
                                     prompt=prompt, max_new_tokens=new,
                                     output=Stamped(s, clock)))
            reqs.extend(batch)
            burst += 1
            srv.serve(batch)
    except WindowClosed:
        pass
    finally:
        clock.stop()
    in_window = ctx.log.since(mark)
    after = clock.counters(0.0)
    stats = stamps.window_stats(served, t0, clock.deadline)
    spread = stamps.spread_notes(stats, served, t0)
    peak = harness.memory_peak(ctx.devices)
    del srv

    record: Dict = {"counters": {k: after[k] - before[k]
                                 for k in ("tokens_out", "decode_steps")}}
    trace = None
    if clock.dir:
        trace = tr.Trace(tr.load(clock.dir))
        shutil.rmtree(clock.dir, ignore_errors=True)
        lo, hi = clock.marks["start"], clock.marks["stop"]
        record["trace"] = trace
        record["traced"] = dict(
            traced_work(served, lo["t"], hi["t"]),
            decode_steps=hi["decode_steps"] - lo["decode_steps"],
            seconds=hi["t"] - lo["t"])

    chk = mix["check"]
    pick = checks.sample_finished(served, ctx.seed, int(chk["min_tokens"]),
                                  int(chk["max_requests"]))
    seqs = [(np.concatenate([np.asarray(reqs[i].prompt, np.int32),
                             np.asarray(reqs[i].output, np.int32)]),
             served[i].prompt_len) for i in pick]
    if seqs:
        gaps = checks.serve_gaps(ctx.family, sizes, params, seqs,
                                 int(mix["seq_capacity"]),
                                 control="fp8" if ctx.control else None)
    else:
        gaps = {"gap": float("inf"), "tokens": 0}
    failed = sum(1 for r in reqs
                 if len(r.output) > r.max_new_tokens
                 or (r.finish_time and len(r.output) != r.max_new_tokens)
                 or any(not 0 <= t < sizes["vocab_size"] for t in r.output))
    return {
        "e2e": dict(stamps.serve_metrics(stats), setup_s=setup_s),
        "record": record, "trace": trace,
        "checks": {"max_logit_gap": (gaps["gap"], float(chk["max_gap"])),
                   "failed_requests": (failed, 0)},
        "attempted": len(reqs), "failed": failed,
        "memory_peak_bytes": peak,
        "control": {"fp8": {"max_logit_gap": gaps.get("control_gap")}},
        "notes": dict(in_window, window_s=ctx.seconds, bursts=burst,
                      tokens=stats["tokens"],
                      finished=sum(s.finished() for s in served),
                      checked_requests=len(pick),
                      checked_tokens=gaps["tokens"], **spread),
    }
