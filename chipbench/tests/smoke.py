"""A benchmark root at smoke sizes, for the CPU tests.

``make_root(tmp)`` lays out ``BENCHMARK.json`` and ``chipbench/`` under
``tmp``: the real references, drivers, metric readers and peaks, and
configurations and traffic mixes of its own at smoke widths.  A cell is
added to it exactly as a later change adds one: a config file, a traffic
file and entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict

from chipbench import harness

REAL = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_LM = {
    "arch": "stablelm-1.6b", "family": "decoder_lm",
    "source": "https://huggingface.co/stabilityai/stablelm-2-1_6b",
    "sizes": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "d_head": 16, "d_ff": 128, "vocab_size": 256, "rope_pct": 0.25,
              "rope_theta": 10000.0, "norm_eps": 1e-5, "act": "swiglu",
              "norm": "layernorm", "tie_embeddings": False},
    "reduced": ["n_layers", "d_model", "n_heads", "n_kv_heads", "d_head",
                "d_ff", "vocab_size"],
}
SMOKE_ENCDEC = {
    "arch": "whisper-small", "family": "encdec",
    "source": "https://arxiv.org/abs/2212.04356",
    "sizes": {"n_layers": 2, "enc_layers": 2, "d_model": 64, "n_heads": 4,
              "n_kv_heads": 4, "d_head": 16, "d_ff": 128, "vocab_size": 256,
              "enc_seq": 16, "norm_eps": 1e-5, "act": "gelu",
              "norm": "layernorm", "pos_scheme": "learned",
              "tie_embeddings": False},
    "reduced": ["n_layers", "enc_layers", "d_model", "n_heads",
                "n_kv_heads", "d_head", "d_ff", "vocab_size", "enc_seq"],
}
# Limits at smoke width, set like the cells' (PERF.md): the program's
# largest reading over 12 seeds (serve) or 8 (train) on the CPU, and the
# float8 control's smallest over 3 or 4: max_logit_gap 0.021 / 0.12,
# loss_rel 0.00038 / 0.0016, grad_norm_rel 0.0043 / 0.021 (half the
# batch: 0.23), update_norm_rel 0.00085 / 0.0041.
SERVE = {
    "driver": "serve", "slots": 3, "seq_capacity": 64, "burst": 6,
    "block": 3, "prompt": {"values": [8, 16]},
    "output": {"lognormal": {"median": 8, "sigma": 0.5}, "min": 3,
               "max": 24},
    "trace": {"start_s": 0.3, "length_s": 0.5},
    "check": {"min_tokens": 40, "max_requests": 6, "max_gap": 0.06},
}
TRAIN = {
    "driver": "train", "batch": 4, "seq": 16, "chunk": 1024,
    "steps_per_call": 1,
    "optimizer": {"peak_lr": 1e-3, "warmup": 2, "total_steps": 100,
                  "weight_decay": 0.1, "clip_norm": 1.0, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8},
    "trace": {"start_s": 0.2, "length_s": 0.5},
    "check": {"block_rows": 2, "loss_rel": 0.0009, "grad_norm_rel": 0.01,
              "update_norm_rel": 0.0025},
}


def write_json(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp: str) -> str:
    """A root whose ``BENCHMARK.json`` has the real one's metrics and no
    cell; ``add_cell`` adds cells."""
    cb = os.path.join(tmp, "chipbench")
    os.makedirs(cb, exist_ok=True)
    for name in ("refs", "drivers", "metrics", "peaks.json"):
        os.symlink(os.path.join(REAL, name), os.path.join(cb, name))
    with open(os.path.join(os.path.dirname(REAL), "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec.update(configs=[], workloads=[])
    write_json(os.path.join(tmp, "BENCHMARK.json"), spec)
    return tmp


def add_cell(root: str, cell: str, config: str, cfg: Dict, mix: str,
             traffic: Dict, like: str, chips: int = 1) -> None:
    """Add a cell as files and ``BENCHMARK.json`` entries only; it reports
    the metrics that cell ``like`` of the real benchmark reports."""
    write_json(os.path.join(root, "chipbench", "configs", config + ".json"),
               cfg)
    write_json(os.path.join(root, "chipbench", "traffic", mix + ".json"),
               traffic)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    if config not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append({
            "name": config, "source": cfg["source"],
            "file": f"chipbench/configs/{config}.json",
            "reduced": cfg["reduced"], "why": "smoke"})
    spec["workloads"].append({"name": cell, "config": config,
                              "traffic": mix, "chips": chips,
                              "why": "smoke"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
    write_json(path, spec)


def run(root: str, cell: str, seed: int = 7, seconds: float = 1.5,
        trace: bool = False):
    """One run on whatever device JAX has, skipping the look for a chip."""
    import jax
    bench = harness.Bench(root)
    return harness.run_cell(bench, cell, seed, seconds, trace,
                            jax.devices()[:1],
                            harness.peaks_for("TPU v5 lite"),
                            harness.CompileLog(), time.perf_counter())


def run_control(root: str, cell: str, seed: int, seconds: float = 1.5):
    """What ``chipbench/control.py`` does for one seed: the driver's
    output with the control's and the faults' readings."""
    import jax
    bench = harness.Bench(root)
    ctx = harness.Context(bench, bench.cell(cell), seed, seconds, False,
                          jax.devices()[:1],
                          harness.peaks_for("TPU v5 lite"),
                          harness.CompileLog(), time.perf_counter())
    ctx.control = True
    return bench.driver(ctx.traffic["driver"]).run(ctx)
