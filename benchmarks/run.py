# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  bench_inference     Fig. 2 / Table 7 (inference accuracy vs time)
  bench_update        plan refresh vs rebuild on a dynamic graph (§10)
  bench_ooc           out-of-core build+serve under an RSS ceiling (§13)
  bench_training      Fig. 3 / Table 7 (per-epoch time, convergence)
  bench_label_rate    Fig. 4 (training-set size scaling)
  bench_batch_size    Fig. 5 (outputs-per-batch sensitivity)
  bench_ablation      Fig. 6 (partitioning ablation)
  bench_scheduling    Fig. 7 (batch scheduling)
  bench_grad_accum    Fig. 8 (gradient accumulation)
  bench_sensitivity   Table 5 (aux-selection hyperparameters)
  bench_memory        Table 6 (main-memory usage)
  bench_kernels       kernel micro-benches
  roofline            dry-run roofline table (reads results/dryrun)

Env: REPRO_BENCH_SCALE=small|paper, REPRO_BENCH_ONLY=<module substring>,
REPRO_BENCH_JSON=<path> (where the kernel rows land as machine-readable
JSON; default <repo>/BENCH_kernels.json) and REPRO_BENCH_INFERENCE_JSON
(inference rows incl. request-latency percentiles and the sustained-load
serve A/B; default <repo>/BENCH_inference.json) — the perf-trajectory
files CI populates on every run. REPRO_BENCH_INFERENCE_SECTION=serve is a
dev fast path that limits bench_inference to the serve-load rows;
REPRO_BENCH_INFERENCE_SECTION=faults limits it to the chaos-drill row the
CI chaos job gates with check_bench_json serve-faults (DESIGN.md §12).
"""
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.ioutil import atomic_write_json  # noqa: E402  (needs src on path)

MODULES = [
    # first: its serving children need the device before this process
    # starts a JAX backend
    "bench_ooc",
    "bench_kernels",
    "bench_memory",
    "bench_inference",
    "bench_update",
    "bench_training",
    "bench_ablation",
    "bench_scheduling",
    "bench_grad_accum",
    "bench_batch_size",
    "bench_label_rate",
    "bench_sensitivity",
    "roofline",
]


def _parse_derived(derived: str) -> dict:
    out = {}
    for kv in derived.split(";"):
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


# modules whose rows land in a machine-readable perf-trajectory JSON:
# mod_name → (env var overriding the path, default filename). Several
# modules may share one file (bench_training's data-parallel rows ride in
# BENCH_kernels.json) — the writer merges by op name instead of clobbering.
_JSON_OUTPUTS = {
    "bench_kernels": ("REPRO_BENCH_JSON", "BENCH_kernels.json"),
    "bench_training": ("REPRO_BENCH_JSON", "BENCH_kernels.json"),
    "bench_inference": ("REPRO_BENCH_INFERENCE_JSON", "BENCH_inference.json"),
    "bench_update": ("REPRO_BENCH_UPDATE_JSON", "BENCH_update.json"),
    "bench_ooc": ("REPRO_BENCH_OOC_JSON", "BENCH_ooc.json"),
}


def _write_bench_json(mod_name, mod, rows) -> None:
    """Machine-readable perf-trajectory file: one record per row with
    (op, wall time + derived stats — backend/tile fill for kernels,
    request-latency percentiles for inference, devices for data-parallel
    rows). Prefers the module's full-precision JSON_RECORDS mirror; parsing
    the display string (%.4g) is only the fallback. Records REPLACE any
    existing record with the same op and leave the rest of the file alone,
    so modules sharing a file (and partial REPRO_BENCH_ONLY runs) never
    erase each other's trajectory."""
    env, default = _JSON_OUTPUTS[mod_name]
    path = os.environ.get(env) or os.path.join(
        os.path.dirname(__file__), "..", default)
    records = getattr(mod, "JSON_RECORDS", None)
    if not records:
        records = []
        for name, us, derived in rows:
            d = _parse_derived(derived)
            records.append({"op": name, "backend": d.pop("backend", None),
                            "us_per_call": us, **d})
    new_ops = {r.get("op") for r in records}
    kept = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                kept = [r for r in json.load(f) if r.get("op") not in new_ops]
        except (ValueError, OSError):
            kept = []
    # tmp + os.replace publish: a bench run killed mid-write must never
    # truncate the perf-trajectory file CI accumulates across runs.
    atomic_write_json(path, kept + records, indent=1)
    print(f"# wrote {os.path.abspath(path)} ({len(records)} new, "
          f"{len(kept)} kept records)", file=sys.stderr, flush=True)


def main() -> int:
    """Run every selected module; exit non-zero if any of them failed."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = os.environ.get("REPRO_BENCH_ONLY", "")
    failed = []
    print("name,us_per_call,derived")
    for mod_name in MODULES:
        if only and only not in mod_name:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run()
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}", flush=True)
            if mod_name in _JSON_OUTPUTS:
                _write_bench_json(mod_name, mod, rows)
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            print(f"{mod_name}/ERROR,0,{type(e).__name__}", flush=True)
            failed.append(mod_name)
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr,
              flush=True)
    if failed:
        print(f"# FAILED: {', '.join(failed)}", file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
