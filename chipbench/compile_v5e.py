"""Compile each cell's jitted steps for a described TPU v5e chip, with no
chip attached, and print what the compiler says they hold in memory.

    JAX_PLATFORMS=cpu python3 chipbench/compile_v5e.py [cell ...]

For every cell of ``BENCHMARK.json`` (or those named): the serving cells'
prefill at each prompt length and their decode step, the training cells'
train step, at the cell's own sizes.  One JSON line per step: argument,
output, temporary and aliased bytes, and their total against the chip's
memory from ``chipbench/peaks.json``.  It runs nothing and times nothing.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def on(sharding, tree):
    import jax
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def steps_of(ctx, sharding):
    """(name, jitted function, argument shapes) of the cell's steps."""
    import jax
    import jax.numpy as jnp
    from chipbench import traffic as T
    from repro.models import build_model
    mix = ctx.traffic
    model = build_model(ctx.arch())
    params = on(sharding, model.param_specs()[0])
    i32 = jnp.int32
    if mix["driver"] == "serve":
        from repro.serve import build_decode_step, build_prefill_step
        cap, slots = int(mix["seq_capacity"]), int(mix["slots"])
        prefill = jax.jit(build_prefill_step(model, seq_capacity=cap))
        for n in T.lengths(mix["prompt"]):
            yield (f"prefill {n}", prefill, (params, on(sharding, {
                "tokens": jax.ShapeDtypeStruct((1, n), i32)})))
        yield (f"decode {slots} x {cap}", jax.jit(build_decode_step(model)),
               (params, on(sharding, {
                   "tokens": jax.ShapeDtypeStruct((slots, 1), i32),
                   "cache": model.cache_spec(slots, cap),
                   "cur_len": jax.ShapeDtypeStruct((slots,), i32)})))
    elif mix["driver"] == "train":
        from repro.configs.base import ShapeConfig
        from repro.train import TrainOptions, build_train_step
        from repro.train.step import train_state_specs
        o = mix["optimizer"]
        opts = TrainOptions(peak_lr=o["peak_lr"], warmup=o["warmup"],
                            total_steps=o["total_steps"],
                            weight_decay=o["weight_decay"],
                            clip_norm=o["clip_norm"], chunk=int(mix["chunk"]))
        state = on(sharding, train_state_specs(model, opts)[0])
        shape = ShapeConfig("cell", int(mix["seq"]), int(mix["batch"]),
                            "train")
        batch = model.input_specs(shape)
        if "enc_embeds" in batch:          # the pipeline sends float32 frames
            batch["enc_embeds"] = jax.ShapeDtypeStruct(
                batch["enc_embeds"].shape, jnp.float32)
        batch["labels"] = jax.ShapeDtypeStruct(batch["tokens"].shape, i32)
        batch["mask"] = jax.ShapeDtypeStruct(batch["tokens"].shape,
                                             jnp.float32)
        yield (f"train {mix['batch']} x {mix['seq']}",
               jax.jit(build_train_step(model, opts), donate_argnums=(0,)),
               (state, on(sharding, batch)))


def main(cells) -> None:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import harness
    jax.config.update("jax_enable_compilation_cache", False)
    bench = harness.Bench(ROOT)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    hbm = harness.peaks_for("TPU v5 lite")["hbm_bytes"]
    for name in cells or [c["name"] for c in bench.spec["workloads"]]:
        cell = bench.cell(name)
        if cell["chips"] != 1:
            continue
        ctx = harness.Context(bench, cell, 0, 0.0, False, [], {}, None, 0.0)
        for step, fn, args in steps_of(ctx, one):
            m = fn.lower(*args).compile().memory_analysis()
            tot = (m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)
            print(json.dumps({
                "cell": name, "step": step,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "total_bytes": tot, "share_of_hbm": tot / hbm}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
