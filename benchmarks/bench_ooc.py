"""Out-of-core plans under an ENFORCED RSS ceiling (DESIGN.md §13).

The claim being measured: a plan whose batch payload exceeds a hard heap
budget can still be built (streaming, chunk-resident) and served
(mmap-backed lazy cache, bounded resident-batch budget) with logits
bitwise identical to the resident engine — and the budget is not a
gentleman's agreement: the serving child runs under
``resource.setrlimit(RLIMIT_DATA, baseline + budget)``, so blowing it is a
MemoryError, not a footnote. (RLIMIT_DATA, not RLIMIT_AS: since Linux 4.7
it caps brk + private anonymous mappings — the heap the resident payload
would live on — while file-backed mmap, the whole point of the store, is
free.)

Rows (→ BENCH_ooc.json, gated by ``check_bench_json --mode ooc``):

  ooc/preprocess_resident   resident build wall time + payload size
  ooc/preprocess_stream     streamed build wall time; fingerprint equality
  ooc/serve_resident        subprocess, NO ceiling: heap growth ≈ payload,
                            p50/p99 request latency, logits hash
  ooc/serve_ooc             subprocess, ceiling ENFORCED: heap growth under
                            budget while payload_mb > budget; p50/p99;
                            logits hash equal to resident (bitwise)
  ooc/serve_shards          in-process shard router: queries span >= 2
                            shards, merged logits bitwise equal resident
  ooc/serve_batch_io_faults scripted ``batch_io`` faults during serving:
                            every injected fault absorbed by bounded
                            retry, zero failed requests

Both serve children replay the SAME seeded request trace with the SAME
seeded params, so sha256(logits) equality is exactly bitwise equality.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import List

import numpy as np

from benchmarks.common import Row, SCALE, fmt
from repro.graph.csr import gcn_preprocess
from repro.graph.datasets import GraphDataset
from repro.graph.synthetic import SyntheticSpec, make_sbm_dataset
from repro.core import IBMBPipeline, IBMBConfig

JSON_RECORDS: List[dict] = []

# bigger than DS_MAIN on purpose: the payload must dwarf the heap budget,
# and features are the payload driver (cache_features=True stores each
# batch's feature rows padded — the thing a resident cache cannot afford)
_SPEC = (SyntheticSpec("ooc-bench", 24_000, 16, 8.0, 256, 0.88,
                       0.35, 0.05, 0.30, seed=11)
         if SCALE == "small" else
         SyntheticSpec("ooc-bench", 80_000, 32, 10.0, 384, 0.88,
                       0.35, 0.05, 0.30, seed=11))
_PIPE_KW = dict(variant="node", k_per_output=8, max_outputs_per_batch=256,
                pad_multiple=64, schedule="none", backend="segment")
_NUM_REQUESTS = 200
_REQUEST_SIZE = 32
_NUM_SHARDS = 3
_RESIDENT_BATCHES = 4


def _record(name: str, us: float, **derived) -> Row:
    JSON_RECORDS.append({"op": name, "us_per_call": float(us), **derived})
    return (name, us, fmt(**derived))


def _dataset() -> GraphDataset:
    g, feats, labels, splits = make_sbm_dataset(_SPEC)
    return GraphDataset(_SPEC.name, g, gcn_preprocess(g), feats, labels,
                        splits)


def _model_cfg_dict(ds) -> dict:
    return dict(kind="gcn", in_dim=int(ds.feat_dim), hidden=64,
                out_dim=int(ds.num_classes), num_layers=2,
                backend="segment")


def _request_trace(ds, seed: int = 0) -> np.ndarray:
    """(R, q) request trace over the train outputs — the same seeded trace
    in parent and both children."""
    rng = np.random.default_rng(seed)
    outs = np.asarray(ds.splits["train"], np.int64)
    return rng.choice(outs, size=(_NUM_REQUESTS, _REQUEST_SIZE))


def _spawn_child(payload: dict) -> dict:
    """Serve in a child on the parent's platform. A device belongs to one
    process, so the parent must not have started a JAX backend yet."""
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "bench_ooc's serving children need the device, but this process "
            "already holds it: run bench_ooc before any other JAX work "
            "(benchmarks/run.py runs it first, or REPRO_BENCH_ONLY=bench_ooc)")
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         json.dumps(payload)],
        capture_output=True, text=True, env=env, timeout=1800)
    for line in proc.stdout.splitlines():
        if line.startswith("OOC_CHILD_RESULT:"):
            return json.loads(line[len("OOC_CHILD_RESULT:"):])
    raise RuntimeError(
        f"serve child ({payload['role']}) died rc={proc.returncode}\n"
        f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-2000:]}")


def run() -> List[Row]:
    from repro.ooc import OOCConfig, PlanStore, build_shards, ShardRouter
    from repro.serve import GNNInferenceEngine
    from repro.faults import FaultInjector
    from repro.models.gnn import GNNConfig, init_gnn
    import jax

    rows: List[Row] = []
    ds = _dataset()
    tmp = tempfile.mkdtemp(prefix="bench_ooc_")
    trace = _request_trace(ds)

    # -- preprocess A/B --------------------------------------------------
    pipe = IBMBPipeline(ds, IBMBConfig(**_PIPE_KW))
    t0 = time.perf_counter()
    resident = pipe.plan("train")
    res_us = (time.perf_counter() - t0) * 1e6
    payload_mb = resident.cache.nbytes() / 2**20
    rows.append(_record("ooc/preprocess_resident", res_us,
                        payload_mb=payload_mb,
                        num_batches=len(resident.cache)))

    store_dir = os.path.join(tmp, "store")
    t0 = time.perf_counter()
    ooc_plan = IBMBPipeline(ds, IBMBConfig(**_PIPE_KW)).plan(
        "train", out_of_core=True, store_dir=store_dir,
        ooc=OOCConfig(chunk_batches=2, resident_batches=_RESIDENT_BATCHES))
    stream_us = (time.perf_counter() - t0) * 1e6
    rows.append(_record(
        "ooc/preprocess_stream", stream_us, payload_mb=payload_mb,
        fingerprint_equal=int(ooc_plan.fingerprint == resident.fingerprint),
        stream_vs_resident=stream_us / max(res_us, 1.0)))

    # the ceiling the ooc child must fit under — well below the payload
    budget_mb = max(32, int(payload_mb / 3))
    assert payload_mb > budget_mb, (payload_mb, budget_mb)

    plan_npz = os.path.join(tmp, "resident_plan.npz")
    resident.save(plan_npz)
    del resident, ooc_plan   # children pay their own materialization

    # -- serve A/B under the harness -------------------------------------
    common = dict(model=_model_cfg_dict(ds), trace=trace.tolist(),
                  resident_batches=_RESIDENT_BATCHES)
    res_child = _spawn_child(dict(common, role="resident",
                                  plan_npz=plan_npz))
    rows.append(_record("ooc/serve_resident", res_child["p50_us"],
                        p99_us=res_child["p99_us"],
                        load_growth_mb=res_child["load_growth_mb"],
                        serve_growth_mb=res_child["serve_growth_mb"],
                        data_growth_mb=res_child["data_growth_mb"],
                        payload_mb=payload_mb, enforced=0))
    ooc_child = _spawn_child(dict(common, role="ooc", store_dir=store_dir,
                                  budget_mb=budget_mb))
    rows.append(_record(
        "ooc/serve_ooc", ooc_child["p50_us"], p99_us=ooc_child["p99_us"],
        load_growth_mb=ooc_child["load_growth_mb"],
        serve_growth_mb=ooc_child["serve_growth_mb"],
        data_growth_mb=ooc_child["data_growth_mb"], payload_mb=payload_mb,
        rss_budget_mb=budget_mb, enforced=1,
        p50_vs_resident=ooc_child["p50_us"] / max(res_child["p50_us"], 1.0),
        logits_equal_resident=int(
            ooc_child["logits_sha"] == res_child["logits_sha"])))

    # -- sharded routing --------------------------------------------------
    mcfg = GNNConfig(**_model_cfg_dict(ds))
    params = init_gnn(mcfg, jax.random.PRNGKey(0))
    root = os.path.join(tmp, "shards")
    build_shards(pipe, "train", _NUM_SHARDS, root,
                 ooc=OOCConfig(chunk_batches=2))
    router = ShardRouter.load(root, mcfg, params,
                              resident_batches=_RESIDENT_BATCHES)
    h = hashlib.sha256()
    lat = []
    hit_min = _NUM_SHARDS + 1
    for req in trace:
        t0 = time.perf_counter()
        out = router.query(req)
        lat.append((time.perf_counter() - t0) * 1e6)
        h.update(np.ascontiguousarray(out).tobytes())
        hit_min = min(hit_min, router.shards_hit(req))
    rows.append(_record(
        "ooc/serve_shards", float(np.percentile(lat, 50)),
        p99_us=float(np.percentile(lat, 99)),
        num_shards=_NUM_SHARDS, shards_hit=router.shards_hit(trace.ravel()),
        shards_hit_min=hit_min,
        logits_equal_resident=int(h.hexdigest()
                                  == res_child["logits_sha"])))

    # -- fault drill: scripted transient read faults ----------------------
    faults = FaultInjector(seed=3, script={"batch_io": [0, 7, 19]})
    store = PlanStore.open(store_dir, faults=faults, io_retries=2)
    engine = GNNInferenceEngine(
        store.as_plan(resident_batches=_RESIDENT_BATCHES), mcfg, params)
    errors = 0
    flat = []
    for req in trace:
        t0 = time.perf_counter()
        try:
            engine.query(req)
        except Exception:
            errors += 1
        flat.append((time.perf_counter() - t0) * 1e6)
    snap = store.stats.snapshot()
    rows.append(_record(
        "ooc/serve_batch_io_faults", float(np.percentile(flat, 50)),
        injected=faults.fired.get("batch_io", 0),
        retries=snap["io_retries"], errors=errors,
        requests=len(trace), reads=snap["reads"]))
    return rows


# --------------------------------------------------------------- the child
def _child(payload: dict) -> None:
    """Serve the request trace in THIS process; for role=ooc, first pin the
    heap: RLIMIT_DATA soft limit = current VmData + budget. Baselines are
    taken after model init + forward warmup, so the ceiling binds exactly
    on what serving allocates — the batch payload."""
    import resource

    import jax
    from repro.core import Plan
    from repro.models.gnn import GNNConfig, init_gnn
    from repro.serve import GNNInferenceEngine

    def vm_mb(key: str) -> float:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith(key + ":"):
                    return int(line.split()[1]) / 1024.0
        return 0.0

    mcfg = GNNConfig(**payload["model"])
    params = init_gnn(mcfg, jax.random.PRNGKey(0))
    trace = np.asarray(payload["trace"], np.int64)
    base_mb = vm_mb("VmData")                    # pre-plan heap watermark

    if payload["role"] == "resident":
        plan = Plan.load(payload["plan_npz"])
    else:
        from repro.ooc import PlanStore
        plan = PlanStore.open(payload["store_dir"]).as_plan(
            resident_batches=payload["resident_batches"])
    load_mb = vm_mb("VmData") - base_mb          # resident: ≈ payload

    engine = GNNInferenceEngine(plan, mcfg, params)
    engine.query(trace[0])                       # compile + first fault-in
    warm_mb = vm_mb("VmData")
    if payload["role"] == "ooc":
        # pin the ceiling ON SERVING: compile/warmup allocations are done,
        # so every further heap byte is batch payload or LRU traffic —
        # exactly what the resident-batch budget claims to bound
        limit = int((warm_mb + payload["budget_mb"]) * 2**20)
        resource.setrlimit(resource.RLIMIT_DATA,
                           (limit, resource.getrlimit(
                               resource.RLIMIT_DATA)[1]))

    h = hashlib.sha256()
    lat = []
    for req in trace:
        t0 = time.perf_counter()
        out = engine.query(req)
        lat.append((time.perf_counter() - t0) * 1e6)
        h.update(np.ascontiguousarray(out).tobytes())
    print("OOC_CHILD_RESULT:" + json.dumps(dict(
        p50_us=float(np.percentile(lat, 50)),
        p99_us=float(np.percentile(lat, 99)),
        load_growth_mb=max(0.0, load_mb),
        serve_growth_mb=max(0.0, vm_mb("VmData") - warm_mb),
        data_growth_mb=max(0.0, vm_mb("VmData") - base_mb),
        rss_mb=vm_mb("VmRSS"), logits_sha=h.hexdigest())))


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "src"))
        _child(json.loads(sys.argv[2]))
    else:
        for r in run():
            print(",".join(map(str, r)))
