"""``correct`` comes out false when the timed path is broken, and the
bfloat16 control fails the committed limits.

Each fault test drives a whole run of a cell through the harness, with no
look for a chip and the configurations cut to a CPU size, with the program
broken underneath at the point where the fault would arise:

* a training step that returns its state unchanged;
* half of each training batch left out of the loss, the mean taken over
  the rest;
* an answer altered where the forward produces it;
* half of a scoring batch's nodes left out of the forward.

The exchange between chips cannot be left out: every cell runs on one
chip. The control is the reference in bfloat16 put in the program's
place, read at the same size.
"""
import os

import jax.numpy as jnp
import pytest

import bench_helpers
from ibmb_bench import harness, logits_check

TRAIN, INFER, SERVE = ("gcn-arxiv.train", "gat-products.infer",
                       "gat-products.serve")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_helpers.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", [TRAIN, INFER, SERVE])
def test_sound_run_is_correct(root, cell):
    res = bench_helpers.run(root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_state_left_unchanged_is_caught(root, monkeypatch):
    import repro.train.gnn_trainer as gt
    monkeypatch.setattr(gt, "apply_updates", lambda params, updates: params)
    res = bench_helpers.run(root, TRAIN)
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] > 0.9


def test_half_the_batch_left_out_is_caught(root, monkeypatch):
    import repro.train.gnn_trainer as gt
    real = gt.masked_xent

    def half(logits, labels, mask):
        first = jnp.cumsum(mask) <= mask.sum() / 2
        return real(logits, labels, mask * first)

    monkeypatch.setattr(gt, "masked_xent", half)
    res = bench_helpers.run(root, TRAIN)
    assert not res["correct"]
    assert res["checks"]["loss_gap"]["value"] > \
        res["checks"]["loss_gap"]["limit"]


@pytest.mark.parametrize("cell", [INFER, SERVE])
def test_altered_answer_is_caught(root, monkeypatch, cell):
    import repro.serve.gnn_engine as ge
    real = ge.output_logits
    monkeypatch.setattr(ge, "output_logits",
                        lambda h, b: real(h, b).at[0, 0].add(1.0))
    res = bench_helpers.run(root, cell)
    assert not res["correct"]


def test_half_the_nodes_left_out_is_caught(root, monkeypatch):
    import repro.serve.gnn_engine as ge
    real = ge.gnn_apply

    def half(cfg, params, batch, **kw):
        n = batch["features"].shape[0]
        keep = (jnp.arange(n) < n // 2)[:, None]
        return real(cfg, params, dict(batch, features=batch["features"]
                                      * keep), **kw)

    monkeypatch.setattr(ge, "gnn_apply", half)
    assert not bench_helpers.run(root, INFER)["correct"]


def _ctx(root, cell, seed):
    bench, entry, config, workload = harness.resolve_cell(root, cell)
    return harness.Ctx(root=root, cell=entry, config=config,
                       workload=workload, seed=seed, seconds=0.0,
                       trace=False, peak={})


@pytest.mark.parametrize("seed", [2**31 + 3, 77])
def test_training_control_fails_a_limit(root, seed):
    mode = harness.load_module(os.path.join(root, "ibmb_bench", "modes",
                                            "train.py"))
    ctx = _ctx(root, TRAIN, seed)
    g, ds, plan = mode.load(ctx)
    _tr, _fit, key, tap = mode.trainer_for(ctx, plan, ds.num_classes, seed)
    lr = ctx.config["optimizer"]["lr"]
    limits = ctx.workload["limits"]
    sound = mode.compare(ctx.model, tap, plan, g, key, lr)
    assert all(sound[n] <= limits[n] for n in limits)
    control = mode.compare(ctx.model, tap, plan, g, key, lr,
                           candidate=jnp.bfloat16)
    assert any(control[n] > limits[n] for n in limits), control


@pytest.fixture(scope="module")
def scoring_control(tmp_path_factory):
    """The control's worst row gap over every test node of the products
    configuration at its published widths and degree, 5,000 nodes (about
    4,500 rows; the gap of the worst row grows with the rows compared, and
    at the CPU test's 600 nodes the control stays under the limit)."""
    from ibmb_bench.modes import infer
    root = bench_helpers.make_root(str(tmp_path_factory.mktemp("wide")),
                                   tiny=False, num_nodes=5000)
    ctx = _ctx(root, INFER, 5)
    g, plan = infer.load(ctx)
    ids = plan.routing.node_ids
    gaps = []
    for seed in (5, 6):
        params = infer.engine_for(ctx, plan, seed)[0]
        rows = [logits_check.rows_of(ids, logits_check.reference_rows(
            ctx.model, params, g, plan, range(len(plan)), dtype=dt))
            for dt in (jnp.float32, jnp.bfloat16)]
        gaps.append(logits_check.worst_row_gap(rows[1], rows[0]))
    return root, gaps


@pytest.mark.parametrize("cell", [INFER, SERVE])
def test_scoring_control_fails_the_limit(scoring_control, cell):
    root, gaps = scoring_control
    limit = _ctx(root, cell, 5).workload["limits"]["logit_gap"]
    assert min(gaps) > limit, gaps
