"""Profiler spans and always-on counters of the serving and training
loops (``BatchServer.serve``, ``Trainer._run_one_step``).

The spans must land on the profiler's host plane and nest as the loops
run; the counters must count one sample per decode step, request or
training step; and neither may change what the loops compute.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, smoke
from repro.configs.base import ShapeConfig
from repro.data import SyntheticPipeline
from repro.models import build_model
from repro.serve import BatchServer, Request
from repro.serve import server as S
from repro.train import TrainOptions, build_train_step, init_train_state
from repro.train import trainer as T

SERVE_SPANS = (S.SPAN_STEP, S.SPAN_FILL, S.SPAN_PREFILL, S.SPAN_INSERT,
               S.SPAN_DISPATCH, S.SPAN_SYNC, S.SPAN_EMIT)
TRAIN_SPANS = (T.SPAN_STEP, T.SPAN_INPUT, T.SPAN_DISPATCH, T.SPAN_SYNC,
               T.SPAN_PREFETCH)
TRAIN_STEPS = 3


class Span:
    def __init__(self, ev, line):
        self.name, self.line = ev.name, line
        self.start, self.end = ev.start_ns, ev.start_ns + ev.duration_ns
        self.stats = dict(ev.stats)

    def inside(self, other: "Span") -> bool:
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end)


def host_spans(trace_dir, names):
    """The host events named in ``names``, in start order, with the
    line each lies on (by index: a plane's thread lines can share a
    name)."""
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(max(files,
                                           key=os.path.getmtime)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [Span(ev, (plane.name, i, line.name))
                    for ev in line.events if ev.name in names]
    return sorted(out, key=lambda s: (s.start, -s.end))


def parent(span, candidates):
    return [c for c in candidates if span.inside(c)]


# ---------------------------------------------------------------------------
# fixtures: one smoke model, served and trained with and without profiler
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_state():
    cfg = smoke(get_config("stablelm-1.6b"))
    model = build_model(cfg)
    opts = TrainOptions(peak_lr=1e-2, warmup=2, total_steps=20, chunk=16)
    state = init_train_state(model, jax.random.PRNGKey(0), opts)
    return cfg, model, opts, state


def requests():
    rng = np.random.RandomState(5)
    lens = (4, 6, 4, 6, 4)
    return [Request(rid=10 + i, prompt=rng.randint(1, 200, n).astype(np.int32),
                    max_new_tokens=3 + i)
            for i, n in enumerate(lens)]


@pytest.fixture(scope="module")
def served(model_state, tmp_path_factory):
    """The same requests served untraced, then traced, by one server."""
    _, model, _, state = model_state
    srv = BatchServer(model=model, params=state["params"], slots=2,
                      seq_capacity=32)
    srv.instantiate()
    plain = srv.serve(requests())
    steps0 = srv.s_decode_steps.value()
    trace_dir = tmp_path_factory.mktemp("serve_trace")
    with jax.profiler.trace(str(trace_dir)):
        traced = srv.serve(requests())
    steps = srv.s_decode_steps.value() - steps0
    return {"srv": srv, "plain": plain, "traced": traced, "steps": steps,
            "first_steps": steps0,
            "spans": host_spans(trace_dir, SERVE_SPANS)}


@pytest.fixture(scope="module")
def trained(model_state, tmp_path_factory):
    """Two trainers from the same state and batches, one traced."""
    cfg, model, opts, state = model_state
    step = build_train_step(model, opts)
    runs = {}
    for traced in (False, True):
        tr = T.Trainer(model=model, train_step=step,
                       pipeline=SyntheticPipeline(
                           cfg, ShapeConfig("smoke", 16, 2, "train"), seed=4),
                       state=jax.tree.map(jnp.copy, state))
        tr.instantiate()
        if traced:
            trace_dir = tmp_path_factory.mktemp("train_trace")
            with jax.profiler.trace(str(trace_dir)):
                tr.run(TRAIN_STEPS)
            runs["spans"] = host_spans(trace_dir, TRAIN_SPANS)
        else:
            tr.run(TRAIN_STEPS)
        runs[traced] = tr
    return runs


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def test_serve_spans_nest_as_the_loop_runs(served):
    spans = served["spans"]
    by = {n: [s for s in spans if s.name == n] for n in SERVE_SPANS}
    steps = by[S.SPAN_STEP]
    assert len(steps) == served["steps"] > 0
    # one of each phase per iteration, in loop order, inside its step
    for name in (S.SPAN_FILL, S.SPAN_DISPATCH, S.SPAN_SYNC, S.SPAN_EMIT):
        assert len(by[name]) == len(steps), name
    for i, st in enumerate(steps):
        phases = [by[n][i] for n in (S.SPAN_FILL, S.SPAN_DISPATCH,
                                     S.SPAN_SYNC, S.SPAN_EMIT)]
        assert all(p.inside(st) for p in phases)
        assert all(a.end <= b.start for a, b in zip(phases, phases[1:]))
        assert st.stats["step_num"] == served["first_steps"] + i
    # all spans on the one thread that ran the loop
    assert len({s.line for s in spans}) == 1


def test_serve_prefill_spans_carry_the_request(served):
    spans = served["spans"]
    fills = [s for s in spans if s.name == S.SPAN_FILL]
    prefills = [s for s in spans if s.name == S.SPAN_PREFILL]
    want = {r.rid: len(r.prompt) for r in requests()}
    assert sorted(p.stats["rid"] for p in prefills) == sorted(want)
    for p in prefills:
        assert p.stats["prompt_len"] == want[p.stats["rid"]]
        assert len(parent(p, fills)) == 1


def test_serve_insert_spans_nest_in_their_prefill(served):
    spans = served["spans"]
    prefills = [s for s in spans if s.name == S.SPAN_PREFILL]
    inserts = [s for s in spans if s.name == S.SPAN_INSERT]
    assert len(inserts) == len(prefills) == len(requests())
    for p in prefills:
        assert len([i for i in inserts if i.inside(p)]) == 1


def test_serve_tokens_identical_with_profiler_on(served):
    plain = {r.rid: r.output for r in served["plain"]}
    traced = {r.rid: r.output for r in served["traced"]}
    assert plain == traced
    assert all(len(plain[r.rid]) == r.max_new_tokens for r in requests())


def test_serve_counters_count_steps_and_requests(served):
    srv = served["srv"]
    steps = srv.s_decode_steps.value()
    assert srv.s_sync_wait.count == srv.s_loop_host.count == steps
    assert srv.s_queue_wait.count == srv.s_requests.value() \
        == 2 * len(requests())
    flat = srv.stats.flat()
    for name in ("sync_wait", "loop_host", "queue_wait"):
        assert flat[f"server.{name}"]["min"] >= 0.0


def test_serve_counts_prefill_tokens_and_cache_bytes(served):
    srv = served["srv"]
    flat = srv.stats.flat()
    assert flat["server.prefill_tokens"] == 2 * sum(
        len(r.prompt) for r in requests())
    # stablelm: k and v of every layer per position, no recurrent state
    cfg = srv.model.cfg
    assert flat["server.cache_kv_bytes_per_slot"] == (
        2 * cfg.n_layers * cfg.n_kv_heads * srv.seq_capacity * cfg.head_dim
        * 2)
    assert flat["server.cache_state_bytes_per_slot"] == 0


def test_serve_request_stamps_are_ordered(served):
    for r in served["traced"]:
        assert r.submit_time <= r.insert_time <= r.finish_time


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def test_train_spans_nest_as_the_step_runs(trained):
    spans = trained["spans"]
    steps = [s for s in spans if s.name == T.SPAN_STEP]
    assert [s.stats["step_num"] for s in steps] == list(range(TRAIN_STEPS))
    for st in steps:
        phases = [s for s in spans if s.name != T.SPAN_STEP and s.inside(st)]
        assert [p.name for p in phases] == [T.SPAN_INPUT, T.SPAN_DISPATCH,
                                            T.SPAN_SYNC]
        assert all(a.end <= b.start for a, b in zip(phases, phases[1:]))
    # the worker builds steps 1.. ahead, on a thread of its own; the last
    # may still be in flight when the profiler stops
    ahead = [s for s in spans if s.name == T.SPAN_PREFETCH]
    assert [s.stats["step_num"] for s in ahead] == \
        list(range(1, len(ahead) + 1))
    assert len(ahead) in (TRAIN_STEPS - 1, TRAIN_STEPS)
    assert all(s.line != steps[0].line for s in ahead)
    assert len(spans) == 4 * TRAIN_STEPS + len(ahead)


def test_train_losses_identical_with_profiler_on(trained):
    plain, traced = trained[False], trained[True]
    assert [h["loss"] for h in plain.history] == \
        [h["loss"] for h in traced.history]
    for a, b in zip(jax.tree.leaves(plain.state["params"]),
                    jax.tree.leaves(traced.state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_input_counter_counts_steps(trained):
    tr = trained[True]
    assert tr.s_input_time.count == tr.s_steps.value() == TRAIN_STEPS
    inputs = [h["input_s"] for h in tr.history]
    assert len(inputs) == TRAIN_STEPS and min(inputs) > 0.0
    assert tr.s_prefetched.value() == TRAIN_STEPS - 1
    assert tr.s_input_time.mean == pytest.approx(np.mean(inputs))
