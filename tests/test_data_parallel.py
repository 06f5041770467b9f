"""Data-parallel Plan execution (DESIGN.md §9): super-step grouping,
ragged-tail padding, shard_map trainer parity vs the single-device loop,
engine mesh routing, and the per-(epoch, step) dropout-rng regression.

Pure-logic tests and 1-device-mesh tests run everywhere (a 1-device mesh
exercises the full shard_map machinery with world=1). The multi-device
parity tests need >= 2 emulated devices — the CI `multidevice` job provides
8 via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; under plain
tier-1 (1 device) they are covered by the @slow subprocess test instead.
"""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import IBMBPipeline, IBMBConfig, Plan
from repro.dist.data_parallel import (
    ShardedPlanExecutor, data_mesh, mesh_world, replicate, stack_batches,
    superstep_indices)
from repro.models.gnn import GNNConfig, init_gnn
from repro.serve import GNNInferenceEngine, GNNRequest
from repro.train import GNNTrainer
from repro.train.gnn_trainer import step_rng

NDEV = jax.device_count()
multidevice = pytest.mark.skipif(
    NDEV < 2, reason="needs >1 device (CI multidevice job emulates 8)")


def _pipe(ds, **kw):
    cfg = dict(variant="node", k_per_output=8, max_outputs_per_batch=16,
               pad_multiple=32)
    cfg.update(kw)
    return IBMBPipeline(ds, IBMBConfig(**cfg))


def _cfg(ds, **kw):
    kw.setdefault("dropout", 0.3)
    return GNNConfig(kind="gcn", in_dim=ds.feat_dim, hidden=32,
                     out_dim=ds.num_classes, num_layers=2, **kw)


# ------------------------------------------------------------ super-steps
def test_superstep_indices_exact_fit():
    steps = superstep_indices(np.array([3, 1, 2, 0]), 2)
    assert len(steps) == 2
    for idx, w in steps:
        assert len(idx) == len(w) == 2
        assert (w == 1.0).all()
    assert np.concatenate([s[0] for s in steps]).tolist() == [3, 1, 2, 0]


def test_superstep_indices_ragged_tail():
    """The tail repeats the LAST REAL batch (same shape bucket) with
    weight 0 — the psum mean must divide by the real count only."""
    (i0, w0), (i1, w1) = superstep_indices(np.array([5, 4, 3, 2, 1]), 4)
    assert i0.tolist() == [5, 4, 3, 2] and (w0 == 1.0).all()
    assert i1.tolist() == [1, 1, 1, 1]
    assert w1.tolist() == [1.0, 0.0, 0.0, 0.0]


def test_superstep_indices_world_one_is_identity():
    steps = superstep_indices(np.array([2, 0, 1]), 1)
    assert [int(s[0][0]) for s in steps] == [2, 0, 1]
    assert all(s[1].tolist() == [1.0] for s in steps)


def test_plan_supersteps_groups_schedule(tiny_ds):
    plan = _pipe(tiny_ds).plan("train")
    steps = plan.supersteps(4)
    assert len(steps) == -(-len(plan) // 4)
    flat = np.concatenate([s[0][s[1] > 0] for s in steps])
    assert np.array_equal(flat, plan.schedule)


def test_stack_batches_cache_fast_path(tiny_ds):
    plan = _pipe(tiny_ds).plan("train")
    idx = np.array([1, 0, 1])
    stacked = stack_batches(plan.cache, idx)
    assert set(stacked) == set(plan.cache.fields)
    for k, v in stacked.items():
        assert v.shape[0] == 3
        assert np.array_equal(v[0], plan.cache[1][k]), k
        assert np.array_equal(v[1], plan.cache[0][k]), k
    # raw-list path gives identical stacks
    listed = stack_batches([plan.cache[i] for i in range(len(plan))], idx)
    for k in stacked:
        assert np.array_equal(stacked[k], listed[k]), k


# ------------------------------------------------------- specs / plumbing
def test_mesh_world_requires_data_axis():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(ValueError, match="data axis"):
        mesh_world(mesh)
    assert mesh_world(data_mesh(1)) == 1


def test_replicate_places_full_tree():
    mesh = data_mesh()
    tree = {"w": np.ones((4, 3), np.float32), "b": np.zeros(3, np.float32)}
    rep = replicate(tree, mesh)
    for leaf in jax.tree_util.tree_leaves(rep):
        assert leaf.sharding.is_fully_replicated
        assert leaf.sharding.mesh == mesh


def test_fit_mesh_rejects_resamplers_and_grad_accum(tiny_ds):
    pipe = _pipe(tiny_ds)
    tr, va = pipe.plan("train"), pipe.plan("val", for_inference=True)
    mesh = data_mesh(1)
    with pytest.raises(ValueError, match="grad_accum"):
        GNNTrainer(_cfg(tiny_ds), grad_accum=2).fit(
            tr, va, tiny_ds.num_classes, epochs=1, mesh=mesh)
    from repro.graph.sampling import make_batcher
    bt = make_batcher("neighbor_sampling", tiny_ds, num_batches=2)
    if not bt.fixed:
        with pytest.raises(ValueError, match="fixed batches"):
            GNNTrainer(_cfg(tiny_ds)).fit(
                bt, va, tiny_ds.num_classes, epochs=1, mesh=mesh)


# ------------------------------------------------------------ rng satellite
def test_step_rng_unique_over_epoch_step_grid():
    """Regression (PR 4 satellite): dropout keys must differ across BOTH
    epochs and steps for a fixed caller rng — the old per-epoch re-split
    replayed identical masks every epoch."""
    base = jax.random.PRNGKey(7)
    keys = {tuple(np.asarray(step_rng(base, ep, st)))
            for ep in range(5) for st in range(7)}
    assert len(keys) == 35
    # and distinct from the init-key domain (fold_in(base, 0))
    assert tuple(np.asarray(jax.random.fold_in(base, 0))) not in keys


def test_fit_fixed_rng_varies_dropout_per_epoch(tiny_ds, monkeypatch):
    """fit() with a fixed caller-passed rng derives a FRESH key per
    (epoch, step): record the keys it consumes and assert epoch 1 differs
    from epoch 0 at every step."""
    import repro.train.gnn_trainer as mod
    seen = []

    def spy(rng, epoch, step):
        k = step_rng(rng, epoch, step)
        seen.append((epoch, step, tuple(np.asarray(k))))
        return k

    monkeypatch.setattr(mod, "step_rng", spy)
    pipe = _pipe(tiny_ds)
    GNNTrainer(_cfg(tiny_ds), lr=1e-3).fit(
        pipe.plan("train"), pipe.plan("val", for_inference=True),
        tiny_ds.num_classes, epochs=2, schedule_mode="none",
        rng=jax.random.PRNGKey(123))
    by_epoch = {}
    for ep, st, k in seen:
        by_epoch.setdefault(ep, {})[st] = k
    assert set(by_epoch) == {0, 1}
    assert by_epoch[0].keys() == by_epoch[1].keys()
    for st in by_epoch[0]:
        assert by_epoch[0][st] != by_epoch[1][st], f"epoch-reused key @ {st}"


# ------------------------------------------------- parity: 1-device mesh
def test_mesh1_fit_matches_plain_fit(tiny_ds):
    """world=1 super-steps ARE per-batch SGD: the shard_map path must
    reproduce the plain jit loop exactly (same Plan, same seed, dropout
    active)."""
    pipe = _pipe(tiny_ds)
    tr, va = pipe.plan("train"), pipe.plan("val", for_inference=True)
    cfg = _cfg(tiny_ds)
    res_m = GNNTrainer(cfg, lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=3, mesh=data_mesh(1))
    res_p = GNNTrainer(cfg, lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=3)
    for hm, hp in zip(res_m.history, res_p.history):
        assert hm["train_loss"] == pytest.approx(hp["train_loss"], abs=1e-6)
        assert hm["val_loss"] == pytest.approx(hp["val_loss"], abs=1e-6)
        assert hm["val_acc"] == pytest.approx(hp["val_acc"], abs=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(res_m.params),
                    jax.tree_util.tree_leaves(res_p.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


# ------------------------------------------------- parity: multi-device
@multidevice
def test_mesh_grad_parity_single_superstep(tiny_ds):
    """One super-step's psum-mean gradients == the mean of the per-batch
    gradients computed serially (segment backend, fp32 tolerance)."""
    from repro.models.gnn.models import gnn_apply, masked_xent, output_logits
    pipe = _pipe(tiny_ds)
    plan = pipe.plan("train")
    world = min(8, NDEV)
    mesh = data_mesh(world)
    cfg = _cfg(tiny_ds, dropout=0.0)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    from repro.optim.optimizers import get_optimizer
    ex = ShardedPlanExecutor(mesh, cfg, get_optimizer("adam"))
    assert ex.sharded and ex.world == world

    def loss_fn(p, b):
        return masked_xent(output_logits(gnn_apply(cfg, p, b), b),
                           b["labels"], b["output_mask"])

    idx, w = ex.supersteps(plan.schedule)[0]
    nreal = int((w > 0).sum())
    want = None
    for i in idx[:nreal]:
        g = jax.grad(loss_fn)(params, plan.cache[int(i)])
        want = g if want is None else jax.tree_util.tree_map(jnp.add, want, g)
    want = jax.tree_util.tree_map(lambda x: x / nreal, want)

    # recover the psum-mean grads through one adam step: compare params
    # after the executor step vs after applying `want` manually. The
    # reference is computed FIRST: `replicate` may zero-copy-alias the
    # original buffers on CPU, and the donating executor step would
    # invalidate them.
    from repro.optim.optimizers import apply_updates
    upd, _ = ex.opt.update(want, ex.opt.init(params), params,
                           jnp.float32(1e-3))
    pw = jax.tree_util.tree_map(np.asarray, apply_updates(params, upd))

    opt_state = ex.replicate(ex.opt.init(params))
    pr = ex.replicate(params)
    batch, wd = ex.stage(plan.cache, idx, w)
    keys = jnp.stack([step_rng(jax.random.PRNGKey(0), 0, j)
                      for j in range(world)])
    p2, _, _ = ex.train_superstep(pr, opt_state, batch, wd,
                                  jnp.float32(1e-3), keys)
    for a, b in zip(jax.tree_util.tree_leaves(p2),
                    jax.tree_util.tree_leaves(pw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@multidevice
def test_mesh_fit_matches_grad_accum_trainer(tiny_ds):
    """Acceptance: on N fake devices the executor-driven fit matches the
    single-device trainer with grad_accum=N — same Plan, same seed, same
    dropout keys — to fp32 tolerance, ragged tail included."""
    world = min(8, NDEV)
    pipe = _pipe(tiny_ds)
    tr, va = pipe.plan("train"), pipe.plan("val", for_inference=True)
    assert len(tr) % world != 0, "want a ragged tail for this test"
    cfg = _cfg(tiny_ds)                          # dropout ACTIVE
    res_m = GNNTrainer(cfg, lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=4, mesh=data_mesh(world))
    res_s = GNNTrainer(cfg, lr=1e-3, seed=0, grad_accum=world).fit(
        tr, va, tiny_ds.num_classes, epochs=4)
    assert len(res_m.history) == len(res_s.history)
    for hm, hs in zip(res_m.history, res_s.history):
        assert hm["train_loss"] == pytest.approx(hs["train_loss"], abs=1e-5)
        assert hm["val_loss"] == pytest.approx(hs["val_loss"], abs=1e-5)
        assert hm["val_acc"] == pytest.approx(hs["val_acc"], abs=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(res_m.params),
                    jax.tree_util.tree_leaves(res_s.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.skipif(NDEV < 4, reason="needs >=4 devices for a 2x2 mesh")
def test_multi_data_axis_mesh_psum_all_axes(tiny_ds):
    """Regression: a ('pod', 'data') mesh must psum gradients over BOTH
    data axes — reducing over 'data' alone lets the 'pod' replicas silently
    diverge (check_vma=False hides it). Parity vs grad_accum=4 pins it."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pod", "data"))
    assert mesh_world(mesh) == 4
    pipe = _pipe(tiny_ds)
    tr, va = pipe.plan("train"), pipe.plan("val", for_inference=True)
    cfg = _cfg(tiny_ds)
    res_m = GNNTrainer(cfg, lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=2, mesh=mesh)
    res_s = GNNTrainer(cfg, lr=1e-3, seed=0, grad_accum=4).fit(
        tr, va, tiny_ds.num_classes, epochs=2)
    for a, b in zip(jax.tree_util.tree_leaves(res_m.params),
                    jax.tree_util.tree_leaves(res_s.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@multidevice
def test_mesh_eval_matches_single_device(tiny_ds):
    pipe = _pipe(tiny_ds)
    plan = pipe.plan("val", for_inference=True)
    cfg = _cfg(tiny_ds, dropout=0.0)
    params = init_gnn(cfg, jax.random.PRNGKey(1))
    ex = ShardedPlanExecutor(data_mesh(min(8, NDEV)), cfg)
    got = ex.evaluate(ex.replicate(params), plan.cache)
    want = GNNTrainer(cfg).evaluate(params, plan)
    assert got["loss"] == pytest.approx(want["loss"], abs=1e-5)
    assert got["acc"] == pytest.approx(want["acc"], abs=1e-6)


@multidevice
def test_engine_mesh_routing_parity(tiny_ds):
    """Engine with a mesh returns the same logits as without, coalesces
    misses into ceil(misses/world) super-steps, and still serves repeat
    traffic from the LRU."""
    plan = _pipe(tiny_ds).plan("test", for_inference=True)
    cfg = _cfg(tiny_ds, dropout=0.0)
    params = init_gnn(cfg, jax.random.PRNGKey(0))
    world = min(8, NDEV)
    test = tiny_ds.splits["test"]
    e1 = GNNInferenceEngine(plan, cfg, params, cache_batches=len(plan))
    em = GNNInferenceEngine(plan, cfg, params, cache_batches=len(plan),
                            mesh=data_mesh(world))
    np.testing.assert_allclose(e1.query(test), em.query(test),
                               atol=1e-5, rtol=1e-5)
    assert em.stats["batch_runs"] == len(plan)
    assert em.stats["supersteps"] == -(-len(plan) // world)
    em.query(test)                               # repeat traffic
    assert em.stats["batch_runs"] == len(plan)
    assert em.stats["lru_hits"] > 0
    # run(): coalesced requests, mesh execution, per-request completion
    reqs = [GNNRequest(node_ids=test), GNNRequest(node_ids=test[:3])]
    em.run(reqs)
    assert all(r.done for r in reqs)
    np.testing.assert_array_equal(reqs[1].logits, reqs[0].logits[:3])


# --------------------------------------------- tier-1 subprocess coverage
_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys; sys.path.insert(0, "src")
import json
import jax, numpy as np
from repro.core import IBMBPipeline, IBMBConfig
from repro.graph.datasets import get_dataset
from repro.models.gnn import GNNConfig
from repro.train import GNNTrainer
from repro.dist.data_parallel import data_mesh

ds = get_dataset("tiny")
pipe = IBMBPipeline(ds, IBMBConfig(variant="node", k_per_output=8,
                                   max_outputs_per_batch=16, pad_multiple=32))
tr, va = pipe.plan("train"), pipe.plan("val", for_inference=True)
cfg = GNNConfig(kind="gcn", in_dim=ds.feat_dim, hidden=32,
                out_dim=ds.num_classes, num_layers=2, dropout=0.3)
rm = GNNTrainer(cfg, lr=1e-3, seed=0).fit(tr, va, ds.num_classes, epochs=3,
                                          mesh=data_mesh())
rs = GNNTrainer(cfg, lr=1e-3, seed=0, grad_accum=8).fit(tr, va,
                                                        ds.num_classes,
                                                        epochs=3)
dl = max(abs(a["val_loss"] - b["val_loss"])
         for a, b in zip(rm.history, rs.history))
dp = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
         for a, b in zip(jax.tree_util.tree_leaves(rm.params),
                         jax.tree_util.tree_leaves(rs.params)))
print(json.dumps({"devices": jax.device_count(), "ragged": len(tr) % 8 != 0,
                  "dloss": dl, "dparam": dp}))
"""


@pytest.mark.slow
def test_8dev_parity_subprocess():
    """Tier-1 stays single-device (conftest note), so the 8-fake-device
    acceptance parity runs in a subprocess — same check the CI multidevice
    job runs in-process."""
    out = subprocess.run([sys.executable, "-c", _SUBPROC], cwd=".",
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 8
    assert res["dloss"] < 1e-5, res
    assert res["dparam"] < 1e-5, res


# ------------------------------------------------------------ loader group
def test_loader_group_staging(tiny_ds):
    from repro.data.loader import PrefetchLoader
    plan = _pipe(tiny_ds).plan("train")
    world = 4
    loader = PrefetchLoader(plan.cache, np.asarray(plan.schedule),
                            group=world)
    steps = list(loader)
    assert len(steps) == len(loader) == -(-len(plan) // world)
    seen = []
    for batch, w in steps:
        assert all(v.shape[0] == world for v in batch.values())
        for idx_pos in range(world):
            if w[idx_pos] > 0:
                seen.append(1)
    assert len(seen) == len(plan)
    # padded tail weights are zero, real ones are one
    tail_w = steps[-1][1]
    assert tail_w[:len(plan) % world or world].tolist() == \
        [1.0] * (len(plan) % world or world)


# ----------------------------------- bcsr under shard_map (DESIGN.md §14)
def _bcsr_pins(**kw):
    """Plan-build pins that force every batch's auto decision to bcsr with
    block_f 0 — the auto-dispatched executable is then config-identical to
    the forced one, so parity below is bitwise."""
    return dict(backend="bcsr", autotune=True, auto_kappa=1e9,
                tune_block_fs=(), **kw)


def test_bcsr_executor_is_sharded():
    """Regression for the retired TODO(bcsr-shard_map): bcsr no longer
    drops off the shard_map super-step path onto a per-device jit loop."""
    cfg = GNNConfig(kind="gcn", in_dim=8, hidden=16, out_dim=4,
                    num_layers=2, backend="bcsr")
    ex = ShardedPlanExecutor(data_mesh(1), cfg)
    assert ex.sharded is True
    for be in ("segment", "dense", "auto"):
        assert ShardedPlanExecutor(data_mesh(1), cfg,
                                   backend=be).sharded is True


def test_mesh1_bcsr_fit_matches_plain_fit(tiny_ds):
    """bcsr through the shard_map path == bcsr through the plain jit loop
    (same Plan, same seed, dropout active) — the bit-identical acceptance
    for retiring the per-device fallback, on a 1-device mesh."""
    pipe = _pipe(tiny_ds, backend="bcsr")
    tr, va = pipe.plan("train"), pipe.plan("val", for_inference=True)
    cfg = _cfg(tiny_ds, backend="bcsr")
    res_m = GNNTrainer(cfg, lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=3, mesh=data_mesh(1))
    res_p = GNNTrainer(cfg, lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=3)
    for hm, hp in zip(res_m.history, res_p.history):
        assert hm["train_loss"] == hp["train_loss"]
        assert hm["val_loss"] == hp["val_loss"]
    for a, b in zip(jax.tree_util.tree_leaves(res_m.params),
                    jax.tree_util.tree_leaves(res_p.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mesh1_eval_bcsr_matches_single_device(tiny_ds):
    pipe = _pipe(tiny_ds, backend="bcsr")
    plan = pipe.plan("val", for_inference=True)
    cfg = _cfg(tiny_ds, dropout=0.0, backend="bcsr")
    params = init_gnn(cfg, jax.random.PRNGKey(1))
    ex = ShardedPlanExecutor(data_mesh(1), cfg)
    got = ex.evaluate(ex.replicate(params), plan.cache)
    want = GNNTrainer(cfg).evaluate(params, plan)
    assert got["loss"] == pytest.approx(want["loss"], abs=1e-6)
    assert got["acc"] == pytest.approx(want["acc"], abs=1e-6)


@multidevice
def test_mesh_bcsr_fit_matches_grad_accum_trainer(tiny_ds):
    """Multi-device acceptance for §14: bcsr super-steps on N fake devices
    match the single-device grad_accum=N trainer to fp32 tolerance."""
    world = min(8, NDEV)
    pipe = _pipe(tiny_ds, backend="bcsr")
    tr, va = pipe.plan("train"), pipe.plan("val", for_inference=True)
    cfg = _cfg(tiny_ds, backend="bcsr")
    res_m = GNNTrainer(cfg, lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=3, mesh=data_mesh(world))
    res_s = GNNTrainer(cfg, lr=1e-3, seed=0, grad_accum=world).fit(
        tr, va, tiny_ds.num_classes, epochs=3)
    for hm, hs in zip(res_m.history, res_s.history):
        assert hm["train_loss"] == pytest.approx(hs["train_loss"], abs=1e-5)
        assert hm["val_loss"] == pytest.approx(hs["val_loss"], abs=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(res_m.params),
                    jax.tree_util.tree_leaves(res_s.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


@multidevice
def test_mesh_auto_matches_forced_bcsr(tiny_ds):
    """Auto dispatch through multi-device super-steps: with decisions
    pinned all-bcsr at block_f 0, backend='auto' is bitwise the forced
    bcsr executor run."""
    world = min(8, NDEV)
    pipe = _pipe(tiny_ds, **_bcsr_pins())
    tr, va = pipe.plan("train"), pipe.plan("val", for_inference=True)
    assert tr.batch_backends() == ["bcsr"] * len(tr)
    cfg = _cfg(tiny_ds)
    res_a = GNNTrainer(cfg, backend="auto", lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=2, mesh=data_mesh(world))
    res_f = GNNTrainer(cfg, backend="bcsr", lr=1e-3, seed=0).fit(
        tr, va, tiny_ds.num_classes, epochs=2, mesh=data_mesh(world))
    for a, b in zip(jax.tree_util.tree_leaves(res_a.params),
                    jax.tree_util.tree_leaves(res_f.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
