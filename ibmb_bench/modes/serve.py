"""Online serving: open-loop requests through ``AsyncGNNEngine`` over one
``GNNInferenceEngine`` with its LRU of batch outputs.

The generator (``traffic.py``) sends each request at its due time from this
thread; a request's latency runs from its due time to the completion of its
future, so a late send counts. Requests still open when the window closes
are waited for, up to a minute. Rejected, expired, failed and never-finished
requests count in ``failed`` and in no latency.

Set-up makes the weights on the device from ``--seed``, compiles the
forward, and sends one request through the front. The check compares a
sample of the completed requests, drawn from the seed and holding the
longest, with the reference.
"""
from __future__ import annotations

import time

import numpy as np

from ibmb_bench import logits_check, traffic
from ibmb_bench.modes import infer

SAMPLE = 200
WAIT_AFTER_S = 60.0


def setup(ctx):
    g, plan = infer.load(ctx)
    params, engine = infer.engine_for(ctx, plan, ctx.seed,
                                      **ctx.workload.get("engine", {}))
    pool = np.asarray(plan.routing.node_ids)
    # compile every decision's forward
    for backend in sorted(set(plan.batch_backends())):
        b = plan.batch_backends().index(backend)
        engine.query(pool[plan.routing.batch == b][:1])
    st = dict(g=g, plan=plan, params=params, engine=engine)
    front_for(ctx, st)
    st["front"].submit("cell", pool[:1]).result(timeout=600)
    st["sched"] = traffic.schedule(ctx.workload["traffic"], ctx.seconds,
                                   ctx.seed, pool)
    ctx.log(f"plan: {len(plan)} batches of {plan.cache.fields['features'].shape[1]} "
            f"nodes, {plan.cache.nbytes() / 2**30:.3f} GiB; "
            f"{len(st['sched'])} requests, "
            f"{sum(len(i) for _, i in st['sched'])} ids due in {ctx.seconds}s")
    return st


def front_for(ctx, st):
    from repro.serve import AsyncGNNEngine, AsyncServeConfig
    st["front"] = AsyncGNNEngine(
        {"cell": st["engine"]},
        AsyncServeConfig(**ctx.workload.get("front", {})))


def window(ctx, st):
    engine, front, sched = st["engine"], st["front"], st["sched"]
    e0 = dict(engine.stats)
    s0 = front.snapshot()
    futs, late = [], []
    base = time.monotonic()
    for due, ids in sched:
        wait = base + due - time.monotonic()
        if wait > 0:
            with ctx.span("bench/sleep"):
                time.sleep(wait)
        with ctx.span("bench/submit"):
            futs.append(front.submit("cell", ids))
        late.append(time.monotonic() - (base + due))
    close = base + ctx.seconds + WAIT_AFTER_S
    lat, done, failed = [], [], 0
    with ctx.span("bench/drain"):
        for (due, ids), fut in zip(sched, futs):
            if not fut.wait(max(0.0, close - time.monotonic())):
                failed += 1
                continue
            try:
                out = fut.result(timeout=0)
            except Exception:
                failed += 1
                continue
            lat.append((due, fut.t_done - (base + due)))
            done.append((ids, out))
    front.close()
    s1 = front.snapshot()
    st["done"] = done
    lat_ms = np.asarray([x for _, x in lat]) * 1e3
    counters = dict(
        lru_hits=engine.stats["lru_hits"] - e0["lru_hits"],
        batch_runs=engine.stats["batch_runs"] - e0["batch_runs"],
        completed=s1["completed"] - s0["completed"],
        rejected=s1["rejected"] - s0["rejected"],
        windows=s1["windows"] - s0["windows"],
        late_ms=[x * 1e3 for x in late],
        latency_by_due=lat)
    ctx.log(f"{len(sched)} requests, {len(done)} done, {failed} failed; "
            f"lru_hits {counters['lru_hits']} batch_runs "
            f"{counters['batch_runs']} windows {counters['windows']}")
    metrics = {}
    if len(lat_ms):
        metrics = {"serve_p95_ms": float(np.percentile(lat_ms, 95)),
                   "serve_p50_ms": float(np.percentile(lat_ms, 50))}
    return dict(metrics=metrics, attempted=len(sched), failed=failed,
                counters=counters, ok=len(lat_ms) > 0)


def sample_of(requests, seed: int):
    """Indices of the requests the check compares: ``SAMPLE`` drawn from
    the seed, and the longest."""
    rng = np.random.default_rng(seed)
    pick = set(rng.choice(len(requests), min(SAMPLE, len(requests)),
                          replace=False).tolist())
    pick.add(int(np.argmax([len(ids) for ids in requests])))
    return sorted(pick)


def check(ctx, st):
    st.pop("front")
    st.pop("engine")
    params = st.pop("params")
    done, plan = st["done"], st["plan"]
    if not done:
        return [dict(name="logit_gap", value=float("inf"),
                     limit=float(ctx.workload["limits"]["logit_gap"]))]
    sample = [done[i] for i in sample_of([i for i, _ in done], ctx.seed)]
    ids = np.concatenate([i for i, _ in sample])
    bidx, _ = plan.routing.lookup(ids)
    ref_rows = logits_check.reference_rows(ctx.model, params, st["g"], plan,
                                           range(len(plan)))
    got = np.concatenate([o for _, o in sample])
    gap = logits_check.worst_row_gap(got, logits_check.rows_of(ids, ref_rows))
    ctx.log(f"compared {len(sample)} requests, {len(ids)} rows from "
            f"{len(set(bidx.tolist()))} batches")
    return [dict(name="logit_gap", value=gap,
                 limit=float(ctx.workload["limits"]["logit_gap"]))]
