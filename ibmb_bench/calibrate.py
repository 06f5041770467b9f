#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from, on the chip.

    python ibmb_bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3

For each seed it runs what the cell's set-up runs and prints, as one JSON
line, the numbers its check compares: for the program (the lower
readings), and for the control seeds also for the control, the reference
in bfloat16 put in the program's place (the upper readings). A training
cell also reads the planted fault of a loss over half of each batch.
Training cells read the first steps of ``fit``; the scoring cell one pass of
``query`` over every test id at the cell's size; the serving cell the rows
of the requests its check samples, answered by an engine with its LRU.
The benchmark's own runs never run this.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from ibmb_bench import harness  # noqa: E402


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="a serving cell's run length, for its requests")
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU: no look for a chip, no compile cache")
    args = ap.parse_args(argv)
    root = args.root
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    if not args.rehearse:
        harness.prepare_environment(root)
    bench, cell, config, workload = harness.resolve_cell(root, args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax.numpy as jnp
    devices, peak = harness.check_device(
        int(cell["chips"]),
        harness.load_json(os.path.join(root, "ibmb_bench", "peaks.json")),
        required=not args.rehearse)
    if not args.rehearse:
        harness.enable_compile_cache()
    ctx = harness.Ctx(root=root, cell=cell, config=config, workload=workload,
                      seed=seeds[0], seconds=0.0, trace=False, peak=peak)
    mode = harness.load_module(os.path.join(root, "ibmb_bench", "modes",
                                            workload["mode"] + ".py"))
    if workload["mode"] == "train":
        g, ds, plan = mode.load(ctx)
        for seed in seeds:
            t0 = time.perf_counter()
            _tr, _fit, key, tap = mode.trainer_for(ctx, plan,
                                                   ds.num_classes, seed)
            lr = config["optimizer"]["lr"]
            row = dict(seed=seed, program=mode.compare(ctx.model, tap, plan,
                                                       g, key, lr))
            if seed in control:
                row["control"] = mode.compare(ctx.model, tap, plan, g, key,
                                              lr, candidate=jnp.bfloat16)
                row["fault_half"] = mode.compare(ctx.model, tap, plan, g,
                                                 key, lr,
                                                 candidate=jnp.float32,
                                                 half=True)
            row["s"] = time.perf_counter() - t0
            print(json.dumps(row), flush=True)
    else:
        import numpy as np
        from ibmb_bench import logits_check
        from ibmb_bench.modes import infer
        g, plan = infer.load(ctx)
        pool = np.asarray(plan.routing.node_ids)
        for seed in seeds:
            t0 = time.perf_counter()
            ids, kw = pool, dict(cache_batches=0)
            if workload["mode"] == "serve":
                # the rows of the requests a run's check samples, answered
                # through an engine with the cell's LRU
                from ibmb_bench import traffic
                from ibmb_bench.modes import serve
                reqs = [i for _, i in traffic.schedule(
                    workload["traffic"], args.seconds, seed, pool)]
                ids = np.concatenate([reqs[i] for i in
                                      serve.sample_of(reqs, seed)])
                kw = workload.get("engine", {})
            params, engine = infer.engine_for(ctx, plan, seed, **kw)
            got = engine.query(ids)
            del engine
            rows = logits_check.reference_rows(ctx.model, params, g, plan,
                                               range(len(plan)))
            ref = logits_check.rows_of(ids, rows)
            row = dict(seed=seed, program=dict(
                logit_gap=logits_check.worst_row_gap(got, ref)))
            if seed in control:
                low = logits_check.reference_rows(
                    ctx.model, params, g, plan, range(len(plan)),
                    dtype=jnp.bfloat16)
                row["control"] = dict(logit_gap=logits_check.worst_row_gap(
                    logits_check.rows_of(ids, low), ref))
            row["s"] = time.perf_counter() - t0
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
