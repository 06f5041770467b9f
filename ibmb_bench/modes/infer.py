"""Bulk scoring: the window is whole passes of
``GNNInferenceEngine(cache_batches=0).query(every test id)``.

``infer_nodes_per_s`` is every output row whose logits reached host memory
over the window's whole time. Set-up makes the weights on the device from
``--seed`` and runs one pass, which compiles the forward of every backend
decision the plan holds. The check compares every row of every pass with
the reference.
"""
from __future__ import annotations

import time

import numpy as np

from ibmb_bench import data, flops, harness, logits_check
from ibmb_bench.reference import gat, gcn


def load(ctx):
    g = data.graph(ctx)
    ds = data.program_dataset(ctx, g)
    data.check_model(ctx, ds)
    return g, data.plan(ctx, ds, ctx.workload["split"], for_inference=True)


def engine_for(ctx, plan, seed: int, **engine_kw):
    """Weights of ``seed`` made on the device, and an engine over them."""
    ref = {"gcn": gcn, "gat": gat}[ctx.model["kind"]]
    params = data.device_weights(ref, ctx.model, harness.seed_key(seed),
                                 random_affine=True)
    from repro.serve import GNNInferenceEngine
    engine = GNNInferenceEngine(plan, data.model_config(ctx), params,
                                backend=data.policy(ctx), **engine_kw)
    return params, engine


def setup(ctx):
    g, plan = load(ctx)
    params, engine = engine_for(ctx, plan, ctx.seed, cache_batches=0)
    ids = np.random.default_rng(ctx.seed).permutation(
        np.asarray(plan.routing.node_ids))
    t0 = time.perf_counter()
    engine.query(ids)
    nodes, edges = flops.real_counts(plan.cache.fields)
    pass_flops = sum(flops.forward_flops(ctx.model, int(n), int(e))
                     for n, e in zip(nodes, edges))
    ctx.log(f"plan: {len(plan)} batches of {plan.cache.fields['features'].shape[1]} "
            f"nodes, {plan.cache.nbytes() / 2**30:.3f} GiB, decisions "
            f"{sorted(set(plan.batch_backends()))}; warm pass "
            f"{time.perf_counter() - t0:.4f}s for {len(ids)} ids")
    return dict(g=g, plan=plan, params=params, engine=engine, ids=ids,
                pass_flops=pass_flops)


def window(ctx, st):
    engine, ids = st["engine"], st["ids"]
    outs = []
    t0 = time.perf_counter()
    while True:
        with ctx.span("bench/query"):
            outs.append(engine.query(ids))
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    dt = time.perf_counter() - t0
    st["outs"] = outs
    passes = len(outs)
    return dict(metrics={"infer_nodes_per_s": passes * len(ids) / dt},
                attempted=passes * len(ids), failed=0,
                counters=dict(passes=passes,
                              batches=passes * len(st["plan"])),
                flops=passes * st["pass_flops"])


def check(ctx, st):
    st.pop("engine")
    params = st.pop("params")
    plan, ids = st["plan"], st["ids"]
    ref_rows = logits_check.reference_rows(ctx.model, params, st["g"], plan,
                                           range(len(plan)))
    ref = logits_check.rows_of(ids, ref_rows)
    gap = max(logits_check.worst_row_gap(out, ref) for out in st["outs"])
    return [dict(name="logit_gap", value=gap,
                 limit=float(ctx.workload["limits"]["logit_gap"]))]
