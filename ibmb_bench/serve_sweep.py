#!/usr/bin/env python3
"""Sweep the offered rate of a serving cell, to find its knee on the chip.

    python ibmb_bench/serve_sweep.py --workload gat-products.serve \
        --seed 5 --seconds 10 --rates 10,20,40,80

One set-up, then for each rate a fresh front over the same engine runs the
cell's window with the workload's traffic at that rate. Prints one JSON
line per rate: requests, rejected and failed, p50/p95 latency, the median
latency of the window's last third over its first third (above 1 the
backlog grows), the generator's lateness and the LRU hit rate. The knee is
the highest rate with nothing rejected and no growing backlog. The
benchmark's own runs never run this.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from ibmb_bench import harness, traffic  # noqa: E402


def main(argv=None):
    import argparse
    import numpy as np
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    root = args.root
    if not args.rehearse:
        harness.prepare_environment(root)
    bench, cell, config, workload = harness.resolve_cell(root, args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    devices, peak = harness.check_device(
        int(cell["chips"]),
        harness.load_json(os.path.join(root, "ibmb_bench", "peaks.json")),
        required=not args.rehearse)
    if not args.rehearse:
        harness.enable_compile_cache()
    ctx = harness.Ctx(root=root, cell=cell, config=config, workload=workload,
                      seed=args.seed, seconds=args.seconds, trace=False,
                      peak=peak)
    mode = harness.load_module(os.path.join(root, "ibmb_bench", "modes",
                                            "serve.py"))
    st = mode.setup(ctx)
    pool = np.asarray(st["plan"].routing.node_ids)
    for rate in [float(r) for r in args.rates.split(",")]:
        params = dict(workload["traffic"], rate_per_s=rate)
        st["sched"] = traffic.schedule(params, args.seconds, args.seed, pool)
        mode.front_for(ctx, st)
        res = mode.window(ctx, st)
        c = res["counters"]
        lat = sorted(c["latency_by_due"])
        third = max(1, len(lat) // 3)
        trend = (float(np.median([x for _, x in lat[-third:]])) /
                 float(np.median([x for _, x in lat[:third]]))) if lat else 0
        served = c["lru_hits"] + c["batch_runs"]
        print(json.dumps(dict(
            rate=rate, requests=res["attempted"], failed=res["failed"],
            rejected=c["rejected"], **res["metrics"], backlog_trend=trend,
            late_p95_ms=float(np.percentile(c["late_ms"], 95)),
            lru_hit_rate=100.0 * c["lru_hits"] / served if served else None,
            requests_per_window=c["completed"] / max(c["windows"], 1))),
            flush=True)


if __name__ == "__main__":
    main()
