import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (including
# repro.*, which imports jax): jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
cell on the production meshes and extract the roofline inputs.

For each cell this driver:
  1. builds the jitted step (train_step / prefill_step / decode_step)
     with full production shardings,
  2. ``.lower(**input_specs).compile()`` — success proves the sharding
     config is coherent (no mismatched specs, no unsupported
     collectives, memory fits),
  3. records ``compiled.memory_analysis()`` / ``cost_analysis()`` plus
     the loop-corrected FLOPs/bytes/collective-bytes from
     ``repro.core.desim.hlo_cost`` (XLA's cost_analysis counts scan
     bodies once — see that module's docstring),
  4. derives the three roofline terms (TPU v5e constants) and the
     collective schedule, and dumps JSON for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k
  python -m repro.launch.dryrun --all            # 40 cells x 2 meshes
  python -m repro.launch.dryrun --all --single-pod-only
"""

import argparse
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import (REGISTRY, SHAPES, cell_runnable, get_config,
                           get_shape)
from repro.core.desim.hlo_cost import analyze_hlo
from repro.dist.sharding import MeshSharder, make_rules
from repro.launch.mesh import describe, make_production_mesh
from repro.launch.train import build_sharded_train
from repro.models import build_model
from repro.serve.step import build_decode_step, build_prefill_step
from repro.train.step import (TrainOptions, default_options_for,
                              train_state_specs)

# TPU v5e per-chip constants (assignment-specified)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
HBM_BYTES = 16e9

# Gradient-accumulation microbatching per train cell: chosen as the
# smallest power of two whose activation temp fits 16 GB HBM (measured
# via the dry-run memory_analysis — see EXPERIMENTS.md §Perf memory
# iterations).  Microbatching also enables compute/reduce-scatter
# overlap across microbatches.
TRAIN_ACCUM = {
    "deepseek-67b": 8,
    "jamba-v0.1-52b": 8,
    "mixtral-8x22b": 16,
    "olmoe-1b-7b": 4,
    "nemotron-4-15b": 2,
    "rwkv6-7b": 2,
    "stablelm-1.6b": 1,
    "minicpm-2b": 1,
    "qwen2-vl-7b": 1,
    "whisper-small": 1,
}


def roofline_terms(cost, n_dev: int) -> Dict[str, Any]:
    compute = cost.flops / PEAK_FLOPS
    memory = cost.bytes / HBM_BW
    # TPU-target variant: pure copy traffic (CPU while-carry copies)
    # is aliased away by TPU buffer assignment
    memory_ex_copies = max(0.0, (cost.bytes - cost.copy_bytes)) / HBM_BW
    coll = cost.collective_bytes / ICI_BW
    dom = max(("compute", compute), ("memory", memory),
              ("collective", coll), key=lambda kv: kv[1])[0]
    return {"compute_s": compute, "memory_s": memory,
            "memory_s_ex_copies": memory_ex_copies, "collective_s": coll,
            "dominant": dom, "bound_s": max(compute, memory, coll),
            "bound_s_ex_copies": max(compute, memory_ex_copies, coll),
            "hlo_flops_per_device": cost.flops,
            "hlo_bytes_per_device": cost.bytes,
            "copy_bytes_per_device": cost.copy_bytes,
            "collective_bytes_per_device": cost.collective_bytes}


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool = False,
                opts: Optional[TrainOptions] = None,
                rules_override: Optional[Dict] = None,
                mesh=None, serve_param_dtype=None) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = cell_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "why": why}

    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    n_dev = int(mesh.devices.size)
    rules = make_rules(cfg, shape, mesh)
    if rules_override:
        rules.mapping.update(rules_override)
    sharder = MeshSharder(mesh, rules)
    model = build_model(cfg)
    if opts is None:
        import dataclasses
        import numpy as _np
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp = int(_np.prod([sizes.get(a, 1) for a in ("pod", "data")]))
        accum = TRAIN_ACCUM.get(arch, 1) if shape.kind == "train" else 1
        # microbatch must stay divisible by the data-parallel ranks
        accum = max(1, min(accum, shape.global_batch // dp))
        opts = dataclasses.replace(
            default_options_for(cfg), accum_steps=accum,
            moment_dtype=("bfloat16" if arch in
                          ("mixtral-8x22b", "jamba-v0.1-52b")
                          else "float32"),
            # adopted hillclimb (cell 2): train_4k fits one KV chunk ->
            # no online-softmax carry traffic (-12% memory term)
            chunk=(4096 if arch == "deepseek-67b"
                   and shape.kind == "train" else 2048))

    t0 = time.perf_counter()
    if shape.kind == "train":
        state_shapes, _ = train_state_specs(model, opts)
        fn, _, _ = build_sharded_train(model, opts, sharder, shape)
        args = (state_shapes, model.input_specs(shape))
    elif shape.kind == "prefill":
        pstep = build_prefill_step(model, sharder, chunk=opts.chunk,
                                   seq_capacity=shape.seq_len)
        p_shapes, p_axes = model.param_specs()
        if serve_param_dtype is not None:
            p_shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, serve_param_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p_shapes)
        p_sh = sharder.param_shardings(p_axes)
        batch_specs = model.input_specs(shape)
        batch_sh = sharder.batch_shardings(batch_specs, cfg)
        fn = jax.jit(pstep, in_shardings=(p_sh, batch_sh))
        args = (p_shapes, batch_specs)
    else:  # decode
        dstep = build_decode_step(model, sharder)
        p_shapes, p_axes = model.param_specs()
        if serve_param_dtype is not None:
            p_shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, serve_param_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p_shapes)
        p_sh = sharder.param_shardings(p_axes)
        batch_specs = model.input_specs(shape)
        batch_sh = sharder.batch_shardings(batch_specs, cfg)
        fn = jax.jit(dstep, in_shardings=(p_sh, batch_sh),
                     donate_argnums=(1,))
        args = (p_shapes, batch_specs)

    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": float(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": float(getattr(ma, "alias_size_in_bytes", 0)),
    }
    mem["per_device_total"] = (mem["argument_bytes"] + mem["output_bytes"]
                               + mem["temp_bytes"] - mem["alias_bytes"])
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    cost = analyze_hlo(hlo)
    rt = roofline_terms(cost, n_dev)

    # useful-FLOPs ratio: MODEL_FLOPS = 6 N D (train) or 2 N D (fwd)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = cfg.model_flops(tokens, backward=True)
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = cfg.model_flops(tokens, backward=False)
    else:
        tokens = shape.global_batch            # one new token per sequence
        model_flops = cfg.model_flops(tokens, backward=False)
    hlo_flops_global = cost.flops * n_dev
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "mesh_desc": describe(mesh),
        "status": "ok",
        "kind": shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "fits_hbm": mem["per_device_total"] <= HBM_BYTES,
        "xla_cost_analysis": {"flops": float(ca.get("flops", 0.0)),
                              "bytes": float(ca.get("bytes accessed", 0.0))},
        "roofline": rt,
        "collectives": cost.collectives,
        "model_flops_global": model_flops,
        "hlo_flops_global": hlo_flops_global,
        "useful_flops_ratio": (model_flops / hlo_flops_global
                               if hlo_flops_global else 0.0),
        "top_dots": [[f, n] for f, n in cost.top_dots[:5]],
        "top_bytes": [[b, n] for b, n in cost.top_bytes[:8]],
        "rules": rules.describe(),
    }
    return result


def run_matrix(single_pod_only: bool = False, out_dir: str = "results/dryrun",
               archs=None, shapes=None) -> None:
    os.makedirs(out_dir, exist_ok=True)
    meshes = [False] if single_pod_only else [False, True]
    archs = archs or sorted(REGISTRY)
    shapes = shapes or list(SHAPES)
    rows = []
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
                try:
                    res = dryrun_cell(arch, shape, multi, mesh=mesh)
                except Exception as e:  # a failure here is a sharding bug
                    res = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi else "single",
                           "status": "FAILED", "error": repr(e)[:500]}
                with open(os.path.join(out_dir, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=1)
                rows.append(res)
                if res["status"] == "ok":
                    r = res["roofline"]
                    print(f"{tag:55s} ok  compile={res['compile_s']:6.1f}s "
                          f"mem={res['memory']['per_device_total']/1e9:6.2f}GB "
                          f"dom={r['dominant']:10s} bound={r['bound_s']:9.4f}s "
                          f"useful={res['useful_flops_ratio']:.2f}",
                          flush=True)
                else:
                    print(f"{tag:55s} {res['status']}: "
                          f"{res.get('why', res.get('error', ''))[:110]}",
                          flush=True)
    n_ok = sum(r["status"] == "ok" for r in rows)
    n_skip = sum(r["status"] == "skipped" for r in rows)
    n_fail = sum(r["status"] == "FAILED" for r in rows)
    print(f"\n== dry-run matrix: {n_ok} ok / {n_skip} skipped "
          f"(documented) / {n_fail} FAILED ==")
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(rows, f, indent=1)
    if n_fail:
        raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(REGISTRY))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    if args.all:
        run_matrix(args.single_pod_only, args.out)
        return
    if not args.arch or not args.shape:
        ap.error("--arch/--shape required unless --all")
    res = dryrun_cell(args.arch, args.shape, args.multi_pod)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
