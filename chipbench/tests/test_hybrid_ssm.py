"""The hybrid state-space family (``refs/hybrid_ssm.py``): its counts by
hand, and a cell of it at smoke width on the CPU, added as files only."""

import json
import os

import pytest

from chipbench import harness
from chipbench import trace as tr
from chipbench.refs import hybrid_ssm as H
from chipbench.tests import smoke

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_HYBRID = {
    "arch": "granite-4.0-h-micro", "family": "hybrid_ssm",
    "source": "https://huggingface.co/ibm-granite/granite-4.0-h-micro",
    "sizes": {"n_layers": 10, "d_model": 64, "n_heads": 4, "n_kv_heads": 1,
              "d_head": 16, "d_ff": 128, "vocab_size": 256,
              "attn_every": 10, "attn_offset": 5, "pos_scheme": "none",
              "ssm_kind": "mamba2", "d_state": 16, "d_conv": 4, "expand": 2,
              "ssm_heads": 8, "ssm_head_dim": 16, "ssm_groups": 1,
              "ssm_chunk": 8, "norm_eps": 1e-5, "act": "swiglu",
              "norm": "rmsnorm", "tie_embeddings": True,
              "residual_scale": 0.22, "embed_scale": 12.0,
              "attn_multiplier": 0.015625, "logits_scaling": 8.0},
    "reduced": ["n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
                "vocab_size", "d_state", "ssm_heads", "ssm_head_dim",
                "ssm_chunk"],
}
# max_gap at smoke width, set as the cells' (PERF.md): the program's
# largest reading over 6 seeds on the CPU, 0.00071, and the float8
# control's smallest over the same seeds, 0.0077
SERVE = dict(smoke.SERVE, prompt={"values": [8, 16]},
             check=dict(smoke.SERVE["check"], max_gap=0.003))


def sizes(name="granite-4.0-h-micro"):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["sizes"]


def test_params_by_hand():
    s = sizes()
    d, di, conv, h = 2048, 4096, 4096 + 2 * 128, 64
    attn = d * 64 * (2 * 32 + 2 * 8)
    mamba = d * (di + conv + h) + (4 + 1) * conv + 3 * h + di + di * d
    mlp = 3 * d * 8192
    assert H.matmul_params(s) == 1_698_375_552 == (
        100352 * d + 2 * attn + 18 * mamba + 20 * mlp)
    assert H.matmul_params(dict(s, n_layers=40)) == 3_191_230_208
    # the program's own count of the published config agrees
    from repro.configs import get_config
    assert get_config("granite-4.0-h-micro").param_counts()["total"] \
        == 3_191_230_208
    assert H.weight_bytes(s) == 2 * 1_698_375_552


def test_state_and_kv_bytes_by_hand():
    s = sizes()
    assert H.n_attention(s) == 2
    # float32 state of 64 heads x 64 x 128 per layer and sequence
    assert 4 * 64 * 64 * 128 == 2_097_152
    # conv: the last 3 bf16 inputs of x, B and C
    assert H.state_bytes_per_seq(s) == 18 * (2_097_152 + 3 * 4352 * 2)
    # k and v of 8 heads x 64, bf16, 2 attention layers
    assert H.kv_bytes_per_token(s) == 4_096


def test_forward_flops_by_hand():
    s = sizes()
    d = 2048
    proj = (2 * d * 64 * 80 + 18 * d * (2 * 4096 + 4352 + 64)
            + 20 * 3 * d * 8192)
    ssm = 18 * (5 * 64 * 64 * 128 + 2 * 4 * 4352)
    # one token attending to 10 positions, head included
    want = 2 * proj + 2 * 100352 * d + 2 * 4 * 32 * 64 * 10 + ssm
    assert H.forward_flops(s, 1, 10) == want
    # a prefill of 3 tokens emits one: the head runs once
    want3 = 3 * (2 * proj + ssm) + 2 * 100352 * d + 2 * 4 * 32 * 64 * 6
    assert H.forward_flops(s, 3, 6, heads=1) == pytest.approx(want3,
                                                              rel=1e-15)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = smoke.make_root(str(tmp_path_factory.mktemp("bench")))
    smoke.add_cell(r, "hy.serve", "hy", SMOKE_HYBRID, "chat", SERVE,
                   like="granite-4.0-h-micro.serve-chat")
    return r


def test_hybrid_cell_is_correct_and_reports_its_metrics(root):
    line, notes = smoke.run(root, "hy.serve", seed=2 ** 31 + 11)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and notes["checked_tokens"] >= 40
    assert set(line["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                    "setup_s"}
    bench = harness.Bench(root)
    layer = {m["name"] for m in bench.per_layer("hy.serve")}
    assert {"hybrid_decode_roofline", "ssd_prefill_roofline",
            "device_idle.serve.insert", "decode_step_ms"} <= layer
    assert not layer & {"decode_step_roofline", "serve_mfu"}


def _module(name, start, dur):
    return tr.Event("/device:TPU:0", tr.MODULES, f"{name}(7)", start, dur)


def _run(events, traced):
    s = sizes()
    window = tr.Event("/host:CPU", "python", tr.WINDOW, 0.0, 10.0)
    return {"trace": tr.Trace([window] + events), "traced": traced,
            "cfg": {"sizes": s}, "counts": H,
            "peak": harness.peaks_for("TPU v5 lite")}


def test_hybrid_decode_roofline_by_hand():
    reader = harness.Bench(os.path.dirname(HERE)).reader(
        "hybrid_decode_roofline")
    s = sizes()
    run = _run([_module("jit_decode_step", 1.0, 0.030),
                _module("jit_decode_step", 2.0, 0.050)],
               {"decode_steps": 2, "decode_tokens": 64,
                "decode_attended": 64 * 1000.0})
    nbytes = (2 * 2 * 1_698_375_552 + 4096 * (64_000 + 64)
              + 2 * H.state_bytes_per_seq(s) * 64)
    least = max(nbytes / 819e9, H.forward_flops(s, 64, 64_000) / 197e12)
    assert reader.read(run) == pytest.approx(100 * (least / 2) / 0.040)
    # a family without recurrent state (the dense reference) reads nothing
    from chipbench.refs import decoder_lm
    assert reader.read(dict(run, counts=decoder_lm)) is None


def test_ssd_prefill_roofline_by_hand():
    reader = harness.Bench(os.path.dirname(HERE)).reader(
        "ssd_prefill_roofline")
    s = sizes()
    run = _run([_module("jit_prefill_step", 1.0, 0.100),
                _module("jit_prefill_step", 2.0, 0.060)],
               {"prefills": 2, "prompt_tokens": 3072,
                "prefill_attended": 2048 * 2049 / 2 + 1024 * 1025 / 2})
    nbytes = (2 * (2 * 1_698_375_552 + H.state_bytes_per_seq(s))
              + 4096 * 3072)
    flops = H.forward_flops(s, 3072, 2048 * 2049 / 2 + 1024 * 1025 / 2,
                            heads=2)
    least = max(nbytes / 819e9, flops / 197e12)
    assert reader.read(run) == pytest.approx(100 * (least / 2) / 0.080)
    assert reader.read(_run([], {"prefills": 2})) is None


def test_hybrid_control_fails_the_limit(root):
    out = smoke.run_control(root, "hy.serve", seed=5)
    gap, limit = out["checks"]["max_logit_gap"]
    assert gap <= limit < out["control"]["fp8"]["max_logit_gap"]
