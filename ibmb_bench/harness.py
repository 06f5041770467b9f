"""Runs one cell of ``BENCHMARK.json`` once: set-up, the measured window,
the correctness check, and the result line.

A cell names a configuration and a workload; the workload names a mode.
Each is a file found by its name, so a later cell, traffic mix or
per-layer metric is new files and new entries, never an edit here:

* ``configs/<config>.json``: the model, the graph generator's parameters,
  the ``IBMBConfig`` fields and the backend policy;
* ``workloads/<cell>.json``: the mode, its traffic parameters and the
  limits of the numbers its check compares;
* ``modes/<mode>.py``: ``setup(ctx)``, ``window(ctx, state)`` and
  ``check(ctx, state)``;
* ``metrics/<metric>.py``: ``read(record)``, which returns the per-layer
  metric or None where the run has nothing for it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_dir(root: str) -> str:
    return os.path.join(root, "ibmb_bench")


def cache_dir(root: str) -> str:
    return os.path.join(bench_dir(root), ".cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "ibmb_bench_" + os.path.abspath(path).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def prepare_environment(root: str) -> None:
    """Before JAX is imported: its compilation cache at a fixed path inside
    the checkout, and the TPU runtime's logs off (they default to a fixed
    path under /tmp)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache_dir(root),
                                                           "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    # every program, however quick to compile, so that only a cell's first
    # run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs lowered or compiled in this process (JAX's own
    monitoring events), so that the window can be shown to compile none."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.count += 1


def seed_key(seed: int):
    """A JAX key that differs for every whole-number seed (PRNGKey alone
    keeps only the low 32 bits)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass
class Ctx:
    root: str
    cell: dict
    config: dict
    workload: dict
    seed: int
    seconds: float
    trace: bool
    peak: dict

    @property
    def name(self) -> str:
        return self.cell["name"]

    @property
    def model(self) -> dict:
        return self.config["model"]

    def log(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", file=sys.stderr, flush=True)

    def span(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def cache_path(self, *parts: str) -> str:
        path = os.path.join(cache_dir(self.root), self.config["name"], *parts)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        return path


def resolve_cell(root: str, name: str):
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    workload = load_json(os.path.join(bench_dir(root), "workloads",
                                      name + ".json"))
    if workload["config"] != cell["config"]:
        raise SystemExit(f"workloads/{name}.json names config "
                         f"{workload['config']!r}, BENCHMARK.json "
                         f"{cell['config']!r}")
    return bench, cell, config, workload


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The metrics of ``kind`` ('end_to_end' or 'per_layer') this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def peak_for(kind: str, peaks: dict) -> dict:
    """The published peaks of ``kind``; a device not in the table is an
    error, never a default."""
    if kind not in peaks:
        raise KeyError(f"device_kind {kind!r} is not in peaks.json "
                       f"(has {sorted(peaks)})")
    return peaks[kind]


def check_device(chips: int, peaks: dict, required: bool = True):
    import jax
    backend = jax.default_backend()
    if required and backend != "tpu":
        raise NoChip(f"JAX backend is {backend!r}, not 'tpu'")
    devices = jax.devices()
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    kind = devices[0].device_kind
    return devices[:chips], (peak_for(kind, peaks) if required else {})


def memory_peak(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks) if peaks else 0


def run_cell(args, root: str = ROOT, t_start: Optional[float] = None,
             on_chip: bool = True) -> Dict:
    """Run the cell and return the result object (the printed line).

    ``on_chip=False`` is for tests on the CPU: no look for a chip, and
    JAX's process-wide compilation cache settings are left alone."""
    t_start = time.perf_counter() if t_start is None else t_start
    if on_chip:
        prepare_environment(root)
    bench, cell, config, workload = resolve_cell(root, args.workload)
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    bdir = bench_dir(root)
    devices, peak = check_device(int(cell["chips"]),
                                 load_json(os.path.join(bdir, "peaks.json")),
                                 required=on_chip)
    if on_chip:
        enable_compile_cache()
    counter = CompileCounter()
    ctx = Ctx(root=root, cell=cell, config=config, workload=workload,
              seed=int(args.seed), seconds=float(args.seconds),
              trace=bool(args.trace), peak=peak)
    mode = load_module(os.path.join(bdir, "modes",
                                    workload["mode"] + ".py"))
    state = mode.setup(ctx)

    trace_dir = os.path.join(cache_dir(root), "traces", ctx.name)
    if ctx.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # no event per Python call
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = counter.count
    t0 = time.perf_counter()
    with ctx.span("bench/window"):
        res = mode.window(ctx, state)
    t1 = time.perf_counter()
    if ctx.trace:
        jax.profiler.stop_trace()
    compiles = counter.count - compiles0
    setup_s = t0 - t_start
    mem = memory_peak(devices)
    ctx.log(f"setup_s={setup_s:.4f} window_s={t1 - t0:.4f} "
            f"compiles_in_window={compiles}")

    checks = mode.check(ctx, state)
    del state
    correct = bool(res.get("ok", True)) and all(
        c["value"] <= c["limit"] for c in checks)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    out: Dict = {"correct": correct, "attempted": int(res["attempted"]),
                 "failed": int(res["failed"])}
    metrics: Dict[str, Dict] = {}
    if not ctx.trace:
        for m in cell_metrics(bench, ctx.name, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" \
                else res["metrics"][m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        from ibmb_bench import trace_reduce
        try:
            summary = trace_reduce.summarize(trace_dir, len(devices))
        except ValueError:
            if on_chip:
                raise
            summary = None          # the CPU has no device plane
        record = dict(trace=summary, counters=res.get("counters", {}),
                      flops=res.get("flops"), peak=peak)
        for m in cell_metrics(bench, ctx.name, "per_layer"):
            reader = load_module(os.path.join(bdir, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            out["breakdown"] = {"device_ops": summary["top_ops"],
                                "idle_gaps": summary["idle_gaps"]}
    out["metrics"] = metrics
    out["device"] = device
    # a number that could not be read (no match, not finite) is printed as
    # 1e300: JSON has no infinity
    out["checks"] = {c["name"]: {"value": c["value"] if math.isfinite(
        c["value"]) else 1e300, "limit": c["limit"]} for c in checks}
    return {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                                "device", *(["breakdown"] if "breakdown" in out
                                            else []), "checks")}


def emit(result: Dict, out=None, err=None) -> None:
    """The compared numbers as the last lines of stderr, then the result as
    the last line of stdout."""
    out = out or sys.stdout
    err = err or sys.stderr
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
