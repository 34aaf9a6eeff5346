"""Operation and byte counts from shapes, and the peaks table."""

import json
import os

import pytest

from chipbench import harness
from chipbench.refs import decoder_lm, encdec

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sizes(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)["sizes"]


def test_stablelm_params_and_kv_bytes_by_hand():
    s = sizes("stablelm-1.6b")
    # embedding and head: 2 x 100352 x 2048; per layer q, k, v, o
    # (4 x 2048 x 2048) and the SwiGLU MLP (3 x 2048 x 5632); 24 layers
    assert decoder_lm.matmul_params(s) == 1_644_167_168
    assert 2 * 100352 * 2048 + 24 * (4 * 2048 ** 2 + 3 * 2048 * 5632) \
        == 1_644_167_168
    # k and v, 32 heads x 64, bf16, 24 layers
    assert decoder_lm.kv_bytes_per_token(s) == 196_608
    assert decoder_lm.weight_bytes(s) == 2 * (1_644_167_168 - 100352 * 2048)


def test_stablelm_forward_flops_by_hand():
    s = sizes("stablelm-1.6b")
    per_layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    # one token attending to 10 positions, head included
    want = 2 * (24 * per_layer + 100352 * 2048) + 24 * 4 * 32 * 64 * 10
    assert decoder_lm.forward_flops(s, 1, 10) == want
    # a prefill of 3 tokens emits one: the head runs once
    want3 = 2 * (24 * per_layer * 3 + 100352 * 2048) + 24 * 4 * 2048 * 6
    assert decoder_lm.forward_flops(s, 3, 6, heads=1) == want3


def test_whisper_train_flops_by_hand():
    s = sizes("whisper-small")
    d, f, v, te, td = 768, 3072, 51865, 1500, 448
    enc = 12 * (2 * (4 * d * d + 2 * d * f) * te + 4 * d * te * te)
    dec = 12 * (2 * (6 * d * d + 2 * d * f) * td + 2 * 2 * d * d * te
                + 4 * d * (td * (td + 1) / 2 + td * te))
    head = 2 * d * v * td
    assert encdec.train_flops(s, 32, 448) == pytest.approx(
        3 * 32 * (enc + dec + head), rel=1e-12)
    # about 1.6 TFLOP per sample (forward and backward, no recompute)
    assert 1.5e12 < encdec.train_flops(s, 1, 448) < 1.7e12
    assert encdec.matmul_params(s) == (12 * (4 * d * d + 2 * d * f)
                                       + 12 * (8 * d * d + 2 * d * f)
                                       + 2 * v * d)


def test_peaks_lookup():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_kind_raises():
    with pytest.raises(harness.UnknownDevice):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(harness.UnknownDevice):
        harness.peaks_for("cpu")
