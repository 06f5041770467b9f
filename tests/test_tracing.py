"""The program's own trace: host spans named ``ibmb/...`` in the profiler's
trace, with the bytes each loader stage put on the device, and named scopes
on the device ops. Counted here on the CPU, from a real trace reduced by the benchmark's
``trace_reduce`` / ``program_trace``."""
import os
import re
import sys

import jax
import numpy as np
import pytest

from conftest import FakeClock
from repro.core import IBMBConfig, IBMBPipeline
from repro.data.loader import PrefetchLoader, batch_nbytes
from repro.models.gnn import GNNConfig, init_gnn
from repro.serve import AsyncGNNEngine, AsyncServeConfig, GNNInferenceEngine
from repro.train import GNNTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
from ibmb_bench import program_trace, trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def plans(tiny_ds):
    pipe = IBMBPipeline(tiny_ds, IBMBConfig(
        variant="node", k_per_output=8, max_outputs_per_batch=32,
        pad_multiple=16))
    return (pipe.plan("train"), pipe.plan("val"),
            pipe.plan("test", for_inference=True))


def _cfg(ds, kind="gcn"):
    return GNNConfig(kind=kind, in_dim=ds.feat_dim, hidden=16,
                     out_dim=ds.num_classes, num_layers=2, heads=2)


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; the trace's ``ibmb/`` spans, each
    name with its events' arguments in time order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    trace = program_trace.load(trace_reduce.find_xplane(str(tmp_path)))
    lo, hi = trace_reduce.window_of(trace.planes)
    args = {}
    for ev, a in sorted(trace.spans, key=lambda s: s[0].start_ns):
        args.setdefault(ev.name, []).append(a)
    return out, program_trace.span_totals(trace, lo, hi), args


def test_fit_and_query_write_their_spans_and_counters(tiny_ds, plans,
                                                      tmp_path):
    train, val, test = plans
    cfg = _cfg(tiny_ds)
    trainer = GNNTrainer(cfg, seed=0)
    trainer.fit(train, val, tiny_ds.num_classes, epochs=1)   # compiles
    first = trainer.step_num

    def work():
        res = trainer.fit(train, val, tiny_ds.num_classes, epochs=2)
        engine = GNNInferenceEngine(test, cfg, res.params, cache_batches=0)
        engine.query(np.asarray(test.routing.node_ids))
        return engine

    engine, spans, args = _traced(tmp_path, work)
    steps, epochs = 2 * len(train), 2
    count = {k: v["count"] for k, v in spans.items()}
    assert count["ibmb/fit/epoch"] == epochs
    assert count["ibmb/fit/schedule"] == epochs
    assert count["ibmb/fit/eval"] == epochs
    assert count["ibmb/fit/step"] == steps
    assert count["ibmb/fit/loss_sync"] == steps
    assert count["ibmb/loader/stage"] == steps
    # one wait per step, and the one per epoch that finds the loader done
    assert count["ibmb/loader/wait"] == steps + epochs
    # global step numbers, carried on across fit calls
    assert first == len(train)
    assert [int(a["step_num"]) for a in args["ibmb/fit/step"]] == list(
        range(first, first + steps))
    assert trainer.step_num == first + steps

    # staged bytes: every field of every batch, once an epoch
    per_epoch = sum(batch_nbytes(train.cache[i]) for i in range(len(train)))
    assert spans["ibmb/loader/stage"]["args"]["bytes"] == epochs * per_epoch

    runs = engine.stats["batch_runs"]
    assert runs == len(test)
    assert count["ibmb/engine/forward"] == count["ibmb/engine/fetch"] == runs
    assert count["ibmb/engine/scatter"] == runs
    assert count["ibmb/engine/route"] == 1
    assert sorted(int(a["batch"]) for a in args["ibmb/engine/forward"]) == \
        list(range(len(test)))


def test_loader_counts_staged_bytes_on_both_paths(tmp_path):
    rng = np.random.default_rng(0)
    batches = [{"x": rng.normal(size=(8, 4)).astype(np.float32),
                "i": np.arange(8, dtype=np.int32)} for _ in range(5)]
    nbytes = 8 * 4 * 4 + 8 * 4

    def work():
        return (len(list(PrefetchLoader(batches))),
                len(list(PrefetchLoader(batches, group=2))))

    (one, grouped), spans, args = _traced(tmp_path, work)
    assert (one, grouped) == (5, 3)             # the tail padded to 2
    stages = args["ibmb/loader/stage"]
    assert spans["ibmb/loader/stage"]["count"] == 5 + 3
    # per batch, then per super-step of two stacked batches
    assert [int(a["bytes"]) for a in stages] == [nbytes] * 5 + [2 * nbytes] * 3
    assert [int(a["batch"]) for a in stages] == [0, 1, 2, 3, 4, 0, 1, 2]


def test_async_window_span_per_window(tiny_ds, plans, tmp_path):
    _train, _val, test = plans
    cfg = _cfg(tiny_ds)
    engine = GNNInferenceEngine(test, cfg, init_gnn(cfg, jax.random.PRNGKey(0)),
                                cache_batches=0)
    clock = FakeClock()
    tier = AsyncGNNEngine({"m": engine}, AsyncServeConfig(window_us=1000.0),
                          clock=clock, start=False)
    ids = np.asarray(test.routing.node_ids)

    def work():
        futs = [tier.submit("m", ids[:3]), tier.submit("m", ids[3:5])]
        clock.advance(0.002)
        tier.step()
        futs.append(tier.submit("m", ids[:1]))
        clock.advance(0.002)
        tier.step()
        tier.close()
        return futs

    futs, spans, args = _traced(tmp_path, work)
    assert all(f.done() for f in futs)
    windows = args["ibmb/serve/window"]
    assert spans["ibmb/serve/window"]["count"] == tier.stats.windows == 2
    assert [int(a["requests"]) for a in windows] == [2, 1]
    assert [int(a["rows"]) for a in windows] == [5, 1]
    assert len({a["window"] for a in windows}) == 2


OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.mark.parametrize("kind,scopes", [
    ("gcn", {"aggregate", "transform", "norm", "loss", "optimizer"}),
    ("gat", {"aggregate", "attention", "transform", "norm", "loss",
             "optimizer"}),
])
def test_named_scopes_reach_the_compiled_train_step(tiny_ds, plans, kind,
                                                    scopes):
    train = plans[0]
    cfg = _cfg(tiny_ds, kind)
    trainer = GNNTrainer(cfg, seed=0)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    hlo = trainer._train_step.lower(
        params, trainer.opt.init(params), train.cache[0], np.float32(1e-3),
        jax.random.PRNGKey(1)).compile().as_text()
    paths = OP_NAME.findall(hlo)
    found = {program_trace.scope_of(p) for p in paths}
    assert scopes <= found
    # backward ops keep the forward's scope inside transpose(jvp(...))
    assert any("transpose(" in p and program_trace.scope_of(p) == "aggregate"
               for p in paths)
    assert any(re.search(r"layer1\b", p) for p in paths)
