"""The benchmark's harness: finds a cell's files by name, checks the
device, runs the cell's driver and prints the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

- ``chipbench/configs/<config>.json``: the sizes as run, the program
  architecture they override, and the reference family;
- ``chipbench/refs/<family>.py``: the plain float32 reference and the
  operation and byte counts of that family, from shapes alone;
- ``chipbench/traffic/<mix>.json``: the driver kind and its parameters;
- ``chipbench/drivers/<kind>.py``: one general driver per kind;
- ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


class BenchError(RuntimeError):
    """The benchmark's own files are missing or inconsistent."""


def load_file_module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> Any:
    if not os.path.isfile(path):
        raise BenchError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str):
        self.root = root
        self.spec = read_json(os.path.join(root, "BENCHMARK.json"))
        self.dir = os.path.join(root, "chipbench")

    def _entry(self, key: str, name: str) -> Dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise BenchError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> Dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> Dict:
        entry = self._entry("configs", name)
        cfg = read_json(os.path.join(self.root, entry["file"]))
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> Dict:
        t = read_json(os.path.join(self.dir, "traffic", name + ".json"))
        t["name"] = name
        return t

    def family(self, cfg: Dict):
        fam = cfg["family"]
        return load_file_module(os.path.join(self.dir, "refs", fam + ".py"),
                                f"chipbench_ref_{fam}")

    def driver(self, kind: str):
        return load_file_module(
            os.path.join(self.dir, "drivers", kind + ".py"),
            f"chipbench_driver_{kind}")

    def reader(self, metric: str):
        return load_file_module(
            os.path.join(self.dir, "metrics", metric + ".py"),
            "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"))

    def _applies(self, metric: Dict, cell: str, e2e: List[str]) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        return metric.get("moves") in e2e if "moves" in metric else True

    def end_to_end(self, cell: str) -> List[Dict]:
        return [m for m in self.spec["end_to_end"]
                if self._applies(m, cell, [])]

    def per_layer(self, cell: str) -> List[Dict]:
        e2e = [m["name"] for m in self.end_to_end(cell)]
        return [m for m in self.spec["per_layer"]
                if self._applies(m, cell, e2e)]


# ---------------------------------------------------------------------------
# device, peaks, compile cache, seeds
# ---------------------------------------------------------------------------

class UnknownDevice(KeyError):
    pass


def peaks_for(kind: str, path: Optional[str] = None) -> Dict:
    table = read_json(path or os.path.join(HERE, "peaks.json"))
    try:
        return table["devices"][kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {kind!r} is not in chipbench/peaks.json "
            f"({sorted(table['devices'])}); no default is assumed") from None


def check_devices(chips: int):
    """The devices to use, or exit non-zero with no result: the
    benchmark never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU (JAX found {devices[0].platform}); "
                 "the benchmark never runs on the CPU")
    try:
        peak = peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        sys.exit(f"chipbench: {e}")
    if len(devices) < chips:
        sys.exit(f"chipbench: the cell asks for {chips} chips, JAX found "
                 f"{len(devices)}")
    return devices[:chips], peak


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or at the fixed ``<checkout>/.jax_cache``, keeping every program,
    the small eager ones too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Counts backend compilations and their seconds, from JAX's own
    monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def mark(self):
        return self.count, self.seconds

    def since(self, mark) -> Dict[str, float]:
        return {"compiles": self.count - mark[0],
                "compile_s": self.seconds - mark[1]}


def jax_key(seed: int, *stream: int):
    """A JAX key from all 64 bits of ``seed`` (``PRNGKey`` keeps 32)."""
    import jax
    seed %= 2 ** 64
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                             seed // 2 ** 32)
    for s in stream:
        key = jax.random.fold_in(key, s)
    return key


def np_rng(seed: int, *stream: int):
    import numpy as np
    return np.random.default_rng([seed % 2 ** 64, *stream])


def memory_peak(devices) -> Optional[int]:
    """The fullest device's peak: its buffers' peak plus the peak of the
    memory the runtime reserves for the programs' temporaries, which the
    TPU allocator counts apart from the buffers in use."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(st["peak_bytes_in_use"]
                         + st.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

class Context:
    """What a driver is given: the cell, its files, the device and the
    clock at which set-up began."""

    def __init__(self, bench: Bench, cell: Dict, seed: int, seconds: float,
                 trace: bool, devices, peak: Dict, log: CompileLog,
                 started: float):
        self.bench = bench
        self.cell = cell
        self.cfg = bench.config(cell["config"])
        self.traffic = bench.traffic(cell["traffic"])
        self.family = bench.family(self.cfg)
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.peak = peak
        self.log = log
        self.started = started
        # set only by chipbench/control.py: also read the control and the
        # planted faults against the reference after the window
        self.control = False

    def arch(self):
        """The program's ``ArchConfig`` with the sizes of the config
        file: the file, not the program's registry, decides what runs."""
        import dataclasses
        from repro.configs import get_config
        base = get_config(self.cfg["arch"])
        fields = {f.name for f in dataclasses.fields(base)}
        return dataclasses.replace(base, **{
            k: v for k, v in self.cfg["sizes"].items() if k in fields})


def compose(bench: Bench, ctx: Context, out: Dict) -> Dict:
    """The result line from what the driver returned."""
    name = ctx.cell["name"]
    metrics: Dict[str, Dict] = {}
    if ctx.trace:
        record = out["record"]
        record.update(cfg=ctx.cfg, counts=ctx.family, peak=ctx.peak,
                      traffic=ctx.traffic, chips=len(ctx.devices))
        for m in bench.per_layer(name):
            value = bench.reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench.end_to_end(name):
            if m["name"] not in out["e2e"]:
                raise BenchError(f"the {ctx.traffic['driver']} driver does "
                                 f"not measure {m['name']}")
            metrics[m["name"]] = {"value": out["e2e"][m["name"]],
                                  "unit": m["unit"]}
    d0 = ctx.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line: Dict[str, Any] = {
        "correct": all(v <= lim for v, lim in out["checks"].values()),
        "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics, "device": device}
    if ctx.trace and out.get("trace") is not None:
        tr = out["trace"]
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        line["breakdown"] = {"device_ops": tr.top_ops(10),
                             "idle_gaps": tr.idle_gaps(10)}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out["checks"].items()}
    return line


def run_cell(bench: Bench, name: str, seed: int, seconds: float, trace: bool,
             devices, peak: Dict, log: CompileLog, started: float):
    """The result line of one run, and notes for an earlier line."""
    cell = bench.cell(name)
    ctx = Context(bench, cell, seed, seconds, trace, devices, peak, log,
                  started)
    out = bench.driver(ctx.traffic["driver"]).run(ctx)
    return compose(bench, ctx, out), out.get("notes", {})


def emit(line: Dict, notes: Dict) -> None:
    print("window " + json.dumps(notes), flush=True)
    for k, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def main(root: str, workload: str, seed: int, seconds: float, trace: bool,
         started: float) -> None:
    bench = Bench(root)
    cell = bench.cell(workload)
    devices, peak = check_devices(int(cell["chips"]))
    cache = use_compile_cache(root)
    log = CompileLog()
    line, notes = run_cell(bench, workload, seed, seconds, trace, devices,
                           peak, log, started)
    emit(line, dict(notes, compile_cache=cache))
