"""The harness at smoke width on the CPU: finding cells by name, the
arithmetic of token stamps, whole runs, and the refusal of a CPU."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import harness, stamps
from chipbench import traffic as T
from chipbench.tests import smoke

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = smoke.make_root(str(tmp_path_factory.mktemp("bench")))
    smoke.add_cell(r, "lm.serve", "lm", smoke.SMOKE_LM, "serve", smoke.SERVE,
                   like="stablelm-1.6b.serve-decode")
    smoke.add_cell(r, "ed.train", "ed", smoke.SMOKE_ENCDEC, "train",
                   smoke.TRAIN, like="whisper-small.train-b32")
    return r


def test_a_cell_added_as_files_is_found(root):
    bench = harness.Bench(root)
    assert bench.cell("lm.serve")["traffic"] == "serve"
    assert bench.config("lm")["sizes"]["d_model"] == 64
    assert bench.traffic("serve")["driver"] == "serve"
    e2e = {m["name"] for m in bench.end_to_end("lm.serve")}
    assert e2e == {"serve_tokens_per_s", "itl_p95_ms", "setup_s"}
    layer = {m["name"] for m in bench.per_layer("lm.serve")}
    assert "decode_step_ms" in layer and "train_mfu" not in layer
    for m in bench.per_layer("lm.serve"):
        assert m["moves"] in e2e
        assert hasattr(bench.reader(m["name"]), "read")
    with pytest.raises(harness.BenchError):
        bench.cell("no.such-cell")


def test_every_real_cell_and_metric_has_its_files():
    bench = harness.Bench(ROOT)
    for cell in bench.spec["workloads"]:
        ctx = harness.Context(bench, cell, 0, 0.0, False, [], {}, None, 0.0)
        assert ctx.arch().d_model == ctx.cfg["sizes"]["d_model"]
        bench.driver(ctx.traffic["driver"])
        e2e = [m["name"] for m in bench.end_to_end(cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = bench.per_layer(cell["name"])
        assert layer and all(m["moves"] in e2e for m in layer)
    for m in bench.spec["per_layer"]:
        bench.reader(m["name"])


def test_stamp_arithmetic_cuts_at_the_deadline():
    a = stamps.Served(4, 3, times=[1.0, 1.5, 2.5])
    b = stamps.Served(4, 5, times=[2.0, 2.1, 2.3, 3.4, 3.6])
    c = stamps.Served(4, 2, times=[3.2, 3.3])
    st = stamps.window_stats([a, b, c], t0=0.0, deadline=3.0)
    assert st["tokens"] == 6
    assert sorted(st["itl_s"]) == pytest.approx([0.1, 0.2, 0.5, 1.0])
    m = stamps.serve_metrics(st)
    assert m["serve_tokens_per_s"] == pytest.approx(2.0)
    assert m["itl_p95_ms"] == pytest.approx(1e3 * (0.5 + 0.85 * 0.5))


def test_spread_notes_show_a_stall():
    a = stamps.Served(4, 9, times=[0.5, 1.0, 1.5, 5.5, 6.0])
    b = stamps.Served(4, 9, times=[0.6, 1.1, 5.6, 6.0, 6.1])
    st = stamps.window_stats([a, b], t0=0.0, deadline=6.0)
    notes = stamps.spread_notes(st, [a, b], t0=0.0, slice_s=2.0)
    assert notes["tokens_per_slice"] == [5, 0, 4]
    q = notes["itl_ms_p50_p90_p95_p99_max"]
    assert q[0] == pytest.approx(500.0) and q[-1] == pytest.approx(4500.0)


def test_bursts_serve_the_same_sizes_for_every_seed():
    mix = smoke.SERVE
    one = T.burst_sizes(mix, 1, 0)
    assert sorted(one) == sorted(T.burst_sizes(mix, 2 ** 33 + 5, 7))
    assert one != T.burst_sizes(mix, 2, 0)
    assert len(one) == mix["burst"]


def test_serve_run_is_correct_and_reports_its_metrics(root):
    line, notes = smoke.run(root, "lm.serve", seed=2 ** 31 + 11)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    assert notes["compiles"] == 0
    assert list(line)[-1] == "checks"


def test_train_run_is_correct_and_reports_its_metrics(root):
    line, notes = smoke.run(root, "ed.train", seed=3)
    assert line["correct"] and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert notes["compiles"] == 0
    assert set(line["checks"]) == {"loss_rel", "grad_norm_rel",
                                   "update_norm_rel", "failed_steps"}


def test_traced_run_reports_per_layer_metrics(root):
    line, _ = smoke.run(root, "lm.serve", seed=4, trace=True)
    assert line["correct"]
    # the CPU has no TPU plane: no device metric is read from it
    assert "decode_step_ms" not in line["metrics"]
    assert "device_idle.serve" not in line["metrics"]
    assert line["metrics"]["tokens_per_decode_step"]["value"] > 0
    assert line["device"]["busy_s"] is None


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "stablelm-1.6b.serve-decode", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for ln in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(ln)
