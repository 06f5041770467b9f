#!/usr/bin/env python3
"""Chip smoke test: IBMB plan -> train -> serve on a TPU, end to end.

    python chip_smoke.py                # one chip: the main path
    python chip_smoke.py --four-chips   # data-parallel training and serving

Runs the paper's GCN (``configs/gnn_gcn.py`` CONFIG: 3 layers, hidden 256,
dropout 0.3) at full width on the arxiv-like stand-in: ogbn-arxiv's 128
features and 40 classes, node count cut to 20k, generated from its seed.
Plans are built with ``IBMBConfig(variant="node", backend="bcsr")`` and
otherwise the defaults (k=16 aux per output, 1024 outputs per training
batch, twice that for inference). Weights are random from ``--seed``.

Every phase must pass. The script exits non-zero, with no result line, when
JAX finds no TPU or any phase fails. The last line of stdout is one JSON
object naming the device, as JAX reports it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.gnn_gcn import CONFIG  # noqa: E402
from repro.core import IBMBConfig, IBMBPipeline  # noqa: E402
from repro.graph.datasets import get_dataset  # noqa: E402
from repro.models.gnn import policy as gnn_policy  # noqa: E402
from repro.models.gnn.models import (  # noqa: E402
    gnn_apply, masked_xent, output_logits)
from repro.models.gnn.policy import BackendPolicy  # noqa: E402
from repro.serve import GNNInferenceEngine  # noqa: E402
from repro.train import GNNTrainer  # noqa: E402
from repro.train.gnn_trainer import step_rng  # noqa: E402

DATASET = "arxiv-like"
EPOCHS = 3              # one chip
MESH_EPOCHS = 2         # four chips, and grad_accum=4 on one
# bcsr vs segment logits, as max|bcsr - segment| / max|segment|. Both run
# in float32, but at default precision the kernel's MXU dot rounds its
# operands to bfloat16 (unit roundoff 2^-9), as XLA's float32 matmuls do on
# TPU, where the segment path sums exactly; 2e-2 allows that error to
# compound through the three layers.
LOGIT_TOL = 2e-2
# the same comparison, and per-leaf relative L2 of the parameter gradients,
# with every matmul at float32 precision: the paths then differ only in
# summation order (unit roundoff 2^-24), and 1e-4 leaves a hundredfold
# margin over the amplification the gradients show at default precision
F32_TOL = 1e-4
# served logits vs the eval pass of the same executable config
SERVE_TOL = 1e-6
# mesh serving vs one chip: the same float32 program per device, fused by
# XLA inside or outside shard_map
MESH_TOL = 1e-5
# four chips vs grad_accum=4 on one: per-leaf relative L2 of the params
# after the run, and of one super-step's mean gradient. Only the order of
# the float32 gradient sums differs.
PARAM_TOL = 1e-3
GRAD_TOL = 1e-4


class PhaseError(RuntimeError):
    """A smoke phase produced a wrong or missing result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


def phase(name: str):
    print(f"== {name}", flush=True)
    return time.perf_counter()


def done(t0: float) -> None:
    print(f"   ok in {time.perf_counter() - t0:.1f}s", flush=True)


def device_phase(want_count: int) -> jax.Device:
    t0 = phase("device")
    backend = jax.default_backend()
    check(backend == "tpu", f"JAX backend is {backend!r}, not 'tpu'")
    devs = jax.devices()
    d = devs[0]
    print(f"   platform={d.platform} device_kind={d.device_kind!r} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    check(len(devs) >= want_count,
          f"need {want_count} devices, JAX sees {len(devs)}")
    done(t0)
    return d


def build_plans(seed: int):
    t0 = phase("plans")
    ds = get_dataset(DATASET)
    print(f"   {DATASET}: {ds.num_nodes} nodes, {ds.graph.num_edges} edges, "
          f"{ds.feat_dim} features, {ds.num_classes} classes", flush=True)
    pipe = IBMBPipeline(ds, IBMBConfig(variant="node", backend="bcsr",
                                       seed=seed))
    plans = {"train": pipe.plan("train"),
             "val": pipe.plan("val", for_inference=True),
             "test": pipe.plan("test", for_inference=True)}
    total = 0
    for name, plan in plans.items():
        tv = plan.cache.fields["tile_vals"]
        total += plan.cache.nbytes()
        backs = plan.batch_backends()
        print(f"   {name}: {len(plan)} batches, nodes/batch "
              f"{plan.cache.fields['features'].shape[1]}, tiles (R, K, B, B)="
              f"{tuple(tv.shape[1:])}, tile payload {tv.nbytes / 2**30:.2f} "
              f"GiB; autotuner: {backs.count('bcsr')} bcsr, "
              f"{backs.count('segment')} segment", flush=True)
    print(f"   host payload of all plans {total / 2**30:.2f} GiB", flush=True)
    done(t0)
    return ds, plans


def model_config(ds):
    return dataclasses.replace(CONFIG, in_dim=ds.feat_dim,
                               out_dim=ds.num_classes)


def finite_tree(tree) -> bool:
    return all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(tree))


def batch_loss(cfg, train: bool):
    def loss(p, b, rng=None):
        h = gnn_apply(cfg, p, b, rng=rng, train=train)
        return masked_xent(output_logits(h, b), b["labels"],
                           b["output_mask"])
    return loss


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def max_leaf_rel_l2(a, b) -> float:
    return max(rel_l2(x, y) for x, y in zip(jax.tree_util.tree_leaves(a),
                                            jax.tree_util.tree_leaves(b)))


def one_chip(args) -> None:
    ds, plans = build_plans(args.seed)
    cfg = model_config(ds)
    test_ids = np.asarray(ds.splits["test"], np.int64)

    t0 = phase(f"train: {EPOCHS} epochs, auto policy")
    trainer = GNNTrainer(cfg, backend=BackendPolicy.auto(), seed=args.seed)
    res = trainer.fit(plans["train"], plans["val"], ds.num_classes,
                      epochs=EPOCHS, eval_every=1)
    check(len(res.history) == EPOCHS, "an epoch recorded no losses")
    for h in res.history:
        print(f"   epoch {h['epoch']}: train_loss {h['train_loss']:.6f} "
              f"val_loss {h['val_loss']:.6f} val_acc {h['val_acc']:.4f}",
              flush=True)
        check(np.isfinite(h["train_loss"]) and np.isfinite(h["val_loss"]),
              f"non-finite loss at epoch {h['epoch']}")
    check(finite_tree(res.params), "non-finite trained params")
    done(t0)

    t0 = phase("serve: auto policy")
    served = GNNInferenceEngine(plans["test"], cfg, res.params,
                                backend=BackendPolicy.auto())
    ids = np.random.default_rng(args.seed).choice(test_ids, size=512,
                                                  replace=False)
    answers = [served.query(q) for q in ids.reshape(8, 64)]
    for a in answers:
        check(a.shape == (64, ds.num_classes) and np.isfinite(a).all(),
              f"bad served logits: shape {a.shape}")
    print(f"   {len(answers)} requests, {len(ids)} node ids, "
          f"{served.stats['batch_runs']} batch forwards, "
          f"{served.stats['lru_hits']} cache hits", flush=True)
    done(t0)

    t0 = phase("eval: fixed bcsr vs fixed segment on the test plan")
    logits, evals = {}, {}
    for name in ("bcsr", "segment"):
        pol = BackendPolicy.fixed(name)
        evals[name] = GNNTrainer(cfg, backend=pol).evaluate(
            res.params, plans["test"])
        eng = GNNInferenceEngine(plans["test"], cfg, res.params,
                                 backend=pol, cache_batches=0)
        logits[name] = eng.query(test_ids)
        print(f"   {name}: {eng.stats['batch_runs']} batches dispatched, "
              f"test loss {evals[name]['loss']:.6f} "
              f"acc {evals[name]['acc']:.4f}", flush=True)
        check(np.isfinite(logits[name]).all(), f"non-finite {name} logits")
        if name == "bcsr":
            hlo = eng._forward.lower(
                eng.params, plans["test"].cache[0]).compile().as_text()
            n_calls = hlo.count("tpu_custom_call")
            print(f"   bcsr forward: {n_calls} tpu_custom_call ops in the "
                  f"compiled HLO", flush=True)
            check(n_calls > 0, "the bcsr forward runs no Pallas kernel")
    seg = logits["segment"]
    err = float(np.abs(logits["bcsr"] - seg).max() / np.abs(seg).max())
    agree = float((logits["bcsr"].argmax(1) == seg.argmax(1)).mean())
    print(f"   max|bcsr - segment| / max|segment| = {err:.3e} "
          f"(tolerance {LOGIT_TOL:.0e}); argmax agreement {agree:.5f}",
          flush=True)
    check(err <= LOGIT_TOL, "bcsr logits disagree with segment")
    # each served id answers from its batch's autotuned backend
    pos = {int(n): i for i, n in enumerate(test_ids)}
    bidx, _ = plans["test"].routing.lookup(ids)
    backs = plans["test"].batch_backends()
    want = np.stack([logits[backs[int(b)]][pos[int(n)]]
                     for n, b in zip(ids, bidx)])
    serve_err = float(np.abs(np.concatenate(answers) - want).max())
    print(f"   served (auto) vs eval logits of the same backend: max abs "
          f"diff {serve_err:.3e} (tolerance {SERVE_TOL:.0e})", flush=True)
    check(serve_err <= SERVE_TOL, "served logits disagree with eval")
    done(t0)

    t0 = phase("float32 matmuls: bcsr vs segment logits and gradients")
    b0 = plans["train"].cache[0]
    f32_logits, grads = {}, {}
    with jax.default_matmul_precision("float32"):
        for name in ("bcsr", "segment"):
            f32_logits[name] = GNNInferenceEngine(
                plans["test"], cfg, res.params,
                backend=BackendPolicy.fixed(name),
                cache_batches=0).query(test_ids)
            # the custom VJP runs the same kernel on the cotangent
            grads[name] = jax.jit(jax.grad(batch_loss(
                gnn_policy.batch_config(cfg, name, 0), train=False)))(
                    res.params, b0)
    seg = f32_logits["segment"]
    err = float(np.abs(f32_logits["bcsr"] - seg).max() / np.abs(seg).max())
    gerr = max_leaf_rel_l2(grads["bcsr"], grads["segment"])
    print(f"   test logits max|bcsr - segment| / max|segment| = {err:.3e}; "
          f"gradients on train batch 0, max per-leaf rel L2 {gerr:.3e} "
          f"(tolerance {F32_TOL:.0e})", flush=True)
    check(err <= F32_TOL and finite_tree(grads["bcsr"]) and gerr <= F32_TOL,
          "bcsr disagrees with segment at float32 precision")
    done(t0)


def four_chips(args) -> None:
    from repro.dist.data_parallel import ShardedPlanExecutor, data_mesh
    from repro.models.gnn import init_gnn
    from repro.optim.optimizers import sgd

    ds, plans = build_plans(args.seed)
    cfg = model_config(ds)
    train = plans["train"]
    mesh = data_mesh(4)
    auto = BackendPolicy.auto()

    t0 = phase("spread: one super-step over four chips")
    # SGD at lr 1 makes the update the psum-mean gradient itself
    ex = ShardedPlanExecutor(mesh, cfg, sgd(0.0), backend=auto)
    order = np.asarray(train.schedule)
    (idx, w), = ex.supersteps(order[:ex.world])
    batch, wd = ex.stage(train.cache, idx, w)
    for j, shard in enumerate(sorted(batch["features"].addressable_shards,
                                     key=lambda s: s.index[0].start)):
        print(f"   device {shard.device.id}: super-step slot {j} holds "
              f"batch {int(idx[j])}, features {shard.data.shape}", flush=True)
    check(len({s.device.id for s in batch["features"].addressable_shards})
          == 4, "the super-step batch is not spread over four devices")
    base_rng = jax.random.PRNGKey(args.seed)
    # host copy: the donating train super-step frees what it is handed
    params = jax.device_get(init_gnn(cfg, jax.random.fold_in(base_rng, 0)))
    keys = jnp.stack([step_rng(base_rng, 0, j) for j in range(ex.world)])
    fns = ex.steps_for(*gnn_policy.superstep_decision(
        ex.decisions(train), idx))
    rep = ex.replicate(params)
    new, _, losses = fns.train(rep, ex.replicate(ex.opt.init(params)), batch,
                               wd, jnp.float32(1.0), keys)
    mesh_grad = jax.tree_util.tree_map(
        lambda p, n: np.asarray(p) - np.asarray(n), params,
        jax.device_get(new))

    grad_one = jax.jit(jax.value_and_grad(batch_loss(cfg, train=True)))
    one_losses, one_grads = [], []
    for j in range(ex.world):
        l, g = grad_one(params, train.cache[int(idx[j])], keys[j])
        one_losses.append(float(l))
        one_grads.append(g)
    for shard in losses.addressable_shards:
        j = shard.index[0].start
        got = float(np.asarray(shard.data)[0])
        print(f"   device {shard.device.id}: loss {got:.6f}, one chip on "
              f"batch {int(idx[j])}: {one_losses[j]:.6f}", flush=True)
        check(abs(got - one_losses[j]) <= 1e-4 * max(1.0, abs(got)),
              f"device {shard.device.id} loss disagrees with one chip")
    check(len({s.device.id for s in losses.addressable_shards}) == 4,
          "the per-device losses do not come from four devices")
    real = [g for g, wj in zip(one_grads, w) if wj > 0]
    mean_grad = jax.tree_util.tree_map(
        lambda *g: np.mean([np.asarray(x) for x in g], axis=0), *real)
    gerr = max_leaf_rel_l2(mesh_grad, mean_grad)
    print(f"   psum-mean gradient vs mean of four one-chip gradients: max "
          f"per-leaf rel L2 {gerr:.3e} (tolerance {GRAD_TOL:.0e})",
          flush=True)
    check(gerr <= GRAD_TOL, "mesh gradient disagrees with one chip")
    done(t0)

    t0 = phase(f"train: {MESH_EPOCHS} epochs on the mesh vs grad_accum=4")
    res_m = GNNTrainer(cfg, backend=auto, seed=args.seed).fit(
        train, plans["val"], ds.num_classes, epochs=MESH_EPOCHS, mesh=mesh)
    res_s = GNNTrainer(cfg, backend=auto, seed=args.seed, grad_accum=4).fit(
        train, plans["val"], ds.num_classes, epochs=MESH_EPOCHS)
    for hm, hs in zip(res_m.history, res_s.history):
        print(f"   epoch {hm['epoch']}: mesh train_loss "
              f"{hm['train_loss']:.6f} val_loss {hm['val_loss']:.6f} | "
              f"grad_accum=4 train_loss {hs['train_loss']:.6f} val_loss "
              f"{hs['val_loss']:.6f}", flush=True)
        check(np.isfinite(hm["train_loss"]) and np.isfinite(hm["val_loss"]),
              f"non-finite mesh loss at epoch {hm['epoch']}")
    check(len(res_m.history) == len(res_s.history) == MESH_EPOCHS,
          "an epoch recorded no losses")
    perr = max_leaf_rel_l2(jax.device_get(res_m.params),
                           jax.device_get(res_s.params))
    print(f"   params, mesh vs grad_accum=4: max per-leaf rel L2 "
          f"{perr:.3e} (tolerance {PARAM_TOL:.0e})", flush=True)
    check(perr <= PARAM_TOL, "mesh training diverges from grad_accum=4")
    done(t0)

    t0 = phase("serve: mesh engine vs one-chip engine on the test plan")
    params = jax.device_get(res_m.params)
    test_ids = np.asarray(ds.splits["test"], np.int64)
    for name in ("segment", "bcsr"):
        pol = BackendPolicy.fixed(name)
        em = GNNInferenceEngine(plans["test"], cfg, params, backend=pol,
                                mesh=mesh, cache_batches=0)
        got = em.query(test_ids)
        want = GNNInferenceEngine(plans["test"], cfg, params, backend=pol,
                                  cache_batches=0).query(test_ids)
        err = float(np.abs(got - want).max() / np.abs(want).max())
        print(f"   {name}: {em.stats['supersteps']} mesh super-steps, "
              f"{em.stats['batch_runs']} batches; max|mesh - one chip| / "
              f"max|one chip| = {err:.3e} (tolerance {MESH_TOL:.0e})",
              flush=True)
        check(np.isfinite(got).all() and err <= MESH_TOL,
              f"mesh {name} logits disagree with one chip")
    done(t0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel phase on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    print(f"   compile cache: {enable_compile_cache()}", flush=True)
    want = 4 if args.four_chips else 1
    dev = device_phase(want)
    (four_chips if args.four_chips else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
